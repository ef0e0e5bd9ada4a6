"""The trace reduction on synthetic traces whose answers are worked out
by hand.  A device plane's op line and module line, and host spans, are
written as an XSpace text proto and read through
``jax.profiler.ProfileData``, as a recorded trace is."""
from __future__ import annotations

import pytest

from bench import trace
from bench.trace import Event


def xspace(device_ops, modules=(), host=(), device="/device:TPU:0"):
    """Text proto of one device plane (ops and modules as
    (name, start_ns, dur_ns)) and a host plane of spans."""
    names = sorted({n for n, _, _ in [*device_ops, *modules, *host]})
    meta = {n: i + 1 for i, n in enumerate(names)}

    def line(i, name, evs):
        body = "".join(
            f"events {{ metadata_id: {meta[n]} offset_ps: {int(s * 1000)} "
            f"duration_ps: {int(d * 1000)} }}\n" for n, s, d in evs)
        return f"lines {{ id: {i} name: \"{name}\" timestamp_ns: 0\n{body}}}\n"

    md = "".join(f"event_metadata {{ key: {v} value {{ id: {v} name: "
                 f"\"{k}\" }} }}\n" for k, v in meta.items())
    dev = (f"planes {{ id: 1 name: \"{device}\"\n"
           + line(1, "XLA Ops", device_ops) + line(2, "XLA Modules", modules)
           + md + "}\n")
    hst = (f"planes {{ id: 2 name: \"/host:CPU\"\n" + line(3, "python", host)
           + md + "}\n")
    return dev + hst


def profile(*a, **k):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(xspace(*a, **k))


# TPU op events carry the HLO text as their name
OPS = [("%fusion.1 = bf16[8,64]{1,0} fusion(%p.1), kind=kLoop", 100, 50),
       ("%fusion.2 = f32[8]{0} fusion(%p.2)", 140, 30),   # overlap: 100-170
       ("%collective-permute-start.3 = (f32[4], f32[4]) "
        "collective-permute-start(%x)", 200, 10),
       ("%collective-permute-done.3.clone = f32[4]{0} "
        "collective-permute-done(%y)", 210, 40),           # 200-250
       ("all-reduce.7", 300, 20), ("copy.1", 400, 100)]    # 300-320, 400-500
HOST = [("window", 50, 500), ("step_dispatch", 170, 25),
        ("loss_readback", 320, 80), ("other", 0, 10)]


def test_read_keeps_device_ops_and_named_host_spans():
    tr = trace.read(profile(OPS, [("jit_step(12)", 100, 400)], HOST),
                    host_names=("window", "step_dispatch", "loss_readback"))
    # the module line's program events are not operations
    assert [e.name for e in tr.ops[0]] == [n for n, _, _ in OPS]
    assert tr.ops[0][0] == Event(OPS[0][0], 100.0, 50.0)
    assert sorted(e.name for e in tr.host) == ["loss_readback",
                                               "step_dispatch", "window"]


def test_busy_is_the_union_and_collectives_are_summed():
    tr = trace.read(profile(OPS))
    ev = tr.ops[0]
    # 70 + 50 + 20 + 100: the two fusions overlap by 20
    assert trace.busy_ns(ev) == 240
    assert trace.collective_ns(ev) == 10 + 40 + 20
    assert trace.op_totals(ev)["fusion.1 bf16[8,64]"] == 50
    assert trace.op_kind(OPS[3][0]) == "collective-permute-done"
    assert trace.op_label(OPS[2][0]) == "collective-permute-start.3"


def test_gaps_are_named_by_the_host_span_covering_most_of_them():
    tr = trace.read(profile(OPS, host=HOST),
                    host_names=("window", "step_dispatch", "loss_readback"))
    lo, hi = 50, 550
    g = trace.gaps(tr.ops[0], lo, hi, [h for h in tr.host
                                       if h.name != "window"])
    # the enclosing window span names no gap in a summary
    assert trace.summarize(tr, lo, hi, [0]).gaps[0] == ("loss_readback", 80)
    # idle: 50-100, 170-200, 250-300, 320-400, 500-550
    assert sorted(d for _, d in g) == [30, 50, 50, 50, 80]
    assert g[0] == ("loss_readback", 80)
    assert ("step_dispatch", 30) in g


def test_summary_over_a_window_clips_and_averages_devices():
    txt = (xspace(OPS, [("jit_step(1)", 100, 400)])
           + xspace([("fusion.1", 100, 200)], [("jit_step(1)", 100, 200)],
                    device="/device:TPU:1").replace("id: 1 name", "id: 3 name")
           .replace("id: 2 name: \"/host", "id: 4 name: \"/host"))
    from jax.profiler import ProfileData
    tr = trace.read(ProfileData.from_text_proto(txt))
    s = trace.summarize(tr, 150, 450, [0, 1])
    # device 0 in [150, 450]: 150-170, 200-250, 300-320, 400-450 = 140
    assert s.busy_ns == {0: 140, 1: 150}
    assert s.idle_share == pytest.approx(1 - 145 / 300)
    assert s.mean_busy_ns == pytest.approx(145)
    bd = s.breakdown()
    # fusion.1: (0 on device 0, clipped away, + 150 on device 1) / 2
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(75e-9)]
    assert all(len(v) <= 10 for v in bd.values())


def test_a_device_with_no_op_in_the_window_is_an_error():
    tr = trace.read(profile(OPS))
    with pytest.raises(ValueError):
        trace.summarize(tr, 1000, 2000, [0])


def test_the_window_counts_the_programs_it_compiles():
    import jax
    import jax.numpy as jnp
    from bench import harness

    class Driver:
        def __init__(self):
            self.f = jax.jit(lambda x: x * 3 + 1)

        def window(self, seconds, traced):
            return self.f(jnp.arange(7.0)).block_until_ready()

    drv = Driver()
    x = jnp.arange(7.0)
    drv.f(x).block_until_ready()        # set-up: compiles the window's
    assert harness._window(drv, 1.0, False)[1] == 0
    drv.f = jax.jit(lambda x: x * 5 - 1)    # a program set-up never saw
    assert harness._window(drv, 1.0, False)[1] >= 1
