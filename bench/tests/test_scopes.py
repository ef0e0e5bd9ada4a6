"""Scope times and named idle gaps on synthetic traces whose answers are
worked out by hand.  A device plane's op line, with each op's scope
path in the ``tf_op`` stat of its event metadata as a TPU trace has it,
and host threads, are written as an XSpace text proto and read through
``bench.scopes``, as a recorded trace is."""
from __future__ import annotations

import pytest

from bench import harness, scopes
from bench.harness import MetricInput, Window
from bench.scopes import HostEvent, Op

FWD = "jit(train_step)/shard_map/jvp(train.loss)/while/body/dot_general"
BWD = ("jit(train_step)/shard_map/transpose(jvp(train.loss))/while/body/"
       "checkpoint/rematted_computation/dot_general")
SYNC = ("jit(train_step)/shard_map/train.grad_sync/"
        "mpix.allreduce.recursive_halving_doubling.shardmap/ppermute")
OPT = "jit(train_step)/train.optimizer/mul"


def xspace(device_ops, threads=(), device="/device:TPU:0"):
    """Text proto of one device plane, its ops as (name, start_ns,
    dur_ns, path), and a host plane with one line per thread, each a
    (name, [(event, start_ns, dur_ns)]).  An op's path is the ``tf_op``
    stat of its event metadata; ref_value is used for every other one,
    as a recorded trace may store a string by reference."""
    def events(evs, meta):
        return "".join(
            f"events {{ metadata_id: {meta[n]} offset_ps: {int(s * 1000)} "
            f"duration_ps: {int(d * 1000)} }}\n" for n, s, d, *_ in evs)

    ops = {}
    for n, _, _, path in device_ops:
        ops.setdefault(n, path)
    meta = {n: i + 1 for i, n in enumerate(ops)}
    refs = {}
    md = ""
    for i, (n, path) in enumerate(ops.items()):
        if not path:
            stat = ""
        elif i % 2:
            refs[path] = 100 + len(refs)
            stat = f"stats {{ metadata_id: 9 ref_value: {refs[path]} }}"
        else:
            stat = f"stats {{ metadata_id: 9 str_value: \"{path}:\" }}"
        md += (f"event_metadata {{ key: {meta[n]} value {{ id: {meta[n]} "
               f"name: \"{n}\" {stat} }} }}\n")
    sm = "stat_metadata { key: 9 value { id: 9 name: \"tf_op\" } }\n" + "".join(
        f"stat_metadata {{ key: {k} value {{ id: {k} name: \"{p}:\" }} }}\n"
        for p, k in refs.items())
    dev = (f"planes {{ id: 1 name: \"{device}\"\n"
           f"lines {{ id: 1 name: \"XLA Ops\" timestamp_ns: 0\n"
           f"{events(device_ops, meta)}}}\n{md}{sm}}}\n")
    names = sorted({e for _, evs in threads for e, _, _ in evs})
    hmeta = {n: i + 1 for i, n in enumerate(names)}
    lines = "".join(
        f"lines {{ id: {i + 2} name: \"{t}\" timestamp_ns: 0\n"
        f"{events(evs, hmeta)}}}\n" for i, (t, evs) in enumerate(threads))
    hmd = "".join(f"event_metadata {{ key: {v} value {{ id: {v} name: "
                  f"\"{k}\" }} }}\n" for k, v in hmeta.items())
    return dev + f"planes {{ id: 2 name: \"/host:CPU\"\n{lines}{hmd}}}\n"


def read(*a, spans=harness.HOST_SPANS, **k):
    return scopes.read(scopes.parse_text(xspace(*a, **k)), spans)


# forward 0-100 and backward 100-300 inside a while op; the grad sync's
# collective-permute start/done and its flatten 300-380, with an
# unscoped async copy 320-340 beside it; the optimizer 380-420; an
# unscoped op of the next step 480-600
OPS = [("%while.4 = (s32[]) while(%t), body=%b", 0, 300, ""),
       ("%fusion.1 = bf16[8,64] fusion(%p)", 0, 100, FWD),
       ("%fusion.2 = bf16[8,64] fusion(%q)", 100, 200, BWD),
       ("%fusion.3 = f32[4,1024] fusion(%g)", 300, 20,
        "jit(train_step)/shard_map/train.grad_sync/concatenate"),
       ("%collective-permute-start.1 = (f32[2,1024]) "
        "collective-permute-start(%f)", 320, 5, SYNC),
       ("%copy-start.2 = (f32[8]) copy-start(%c)", 320, 20, ""),
       ("%collective-permute-done.1 = f32[2,1024] "
        "collective-permute-done(%s)", 325, 55, SYNC),
       ("%fusion.4 = bf16[8,64] fusion(%u)", 380, 40, OPT),
       ("%fusion.5 = f32[8] fusion(%v)", 480, 120, "")]
MAIN = ("python3", [("window", 0, 600), ("train", 0, 420),
                    ("step_dispatch", 420, 60),
                    ("PjitFunction(train_step)", 425, 50),
                    ("$pjit.py:1 cache_miss", 426, 10),
                    ("loss_readback", 480, 40),
                    ("$array.py:337 __getitem__", 525, 70)])
OTHER = ("futex-worker/12", [("PjitFunction(other)", 400, 200)])


def test_read_takes_paths_from_the_event_metadata_and_one_thread():
    tr = read(OPS, threads=[MAIN, OTHER])
    assert [o.path for o in tr.ops[0]] == [p for *_, p in OPS]
    assert tr.ops[0][1] == Op(OPS[1][0], 0.0, 100.0, FWD)
    # the thread that holds no benchmark span is left out
    assert {h.thread for h in tr.host} == {"python3"}
    assert len(tr.host) == len(MAIN[1])
    assert scopes.scope_path("jit(f)/train.loss/add:") == "jit(f)/train.loss/add"


def test_forward_and_backward_are_told_apart_by_the_transpose():
    busy, _ = scopes.scope_times(read(OPS).ops[0])
    assert busy["jvp(train.loss)"] == 100
    assert busy["transpose(jvp(train.loss))"] == 200
    assert "train.loss" not in busy


def test_scope_busy_and_exposed_with_async_and_overlapping_ops():
    busy, exposed = scopes.scope_times(read(OPS).ops[0])
    # 300-320 flatten, 320-325 start, 325-380 done
    assert busy["train.grad_sync"] == 80
    # the unscoped async copy 320-340 hides 20 of it; the while op
    # around the forward and backward passes hides nothing
    assert exposed["train.grad_sync"] == 60
    m = "mpix.allreduce.recursive_halving_doubling.shardmap"
    assert busy[m] == 60 and exposed[m] == 40
    assert busy["train.optimizer"] == exposed["train.optimizer"] == 40
    # forward and backward overlap nothing but the while op
    assert exposed["jvp(train.loss)"] == 100


def test_summary_shares_gaps_and_the_mpix_scopes():
    tr = read(OPS, threads=[MAIN, OTHER])
    s = scopes.summarize(tr, 0, 600, [0], harness.HOST_SPANS)
    assert s.busy_ns == {0: 540}
    # the while op's own time is covered by its body, the copy 320-340
    # by the sync's ops: only the last op is under no scope
    assert s.unscoped_share == pytest.approx(120 / 540)
    assert s.mpix_scopes() == [
        "mpix.allreduce.recursive_halving_doubling.shardmap"]
    assert s.idle_share == pytest.approx(1 - 540 / 600)
    # idle 420-480 under step_dispatch; the dispatch 425-475 inside it
    # covers more than half, the cache miss 426-436 does not
    assert s.gaps == [("step_dispatch/PjitFunction(train_step)", 60)]
    assert s.gap_totals() == {"step_dispatch/PjitFunction(train_step)": 60}
    # the async copy and the last op; the while op is left out
    assert s.unscoped_op_ns == {"fusion.5 f32[8]": 120, "copy-start.2": 20}
    assert s.host_ns == {"step_dispatch": [60], "loss_readback": [40],
                         "PjitFunction(train_step)": [50]}


def test_each_gap_is_named_by_its_innermost_host_event():
    ops = [Op("a", 0, 10), Op("b", 70, 10), Op("c", 130, 10),
           Op("d", 200, 10)]
    host = [HostEvent("window", 0, 210, "m"),
            HostEvent("step_dispatch", 5, 70, "m"),
            HostEvent("PjitFunction(train_step)", 10, 60, "m"),
            HostEvent("$array.py:337 __getitem__", 12, 55, "m"),
            HostEvent("step_dispatch", 80, 50, "m"),
            HostEvent("$tiny", 85, 5, "m"),
            HostEvent("loss_readback", 140, 60, "m"),
            HostEvent("PjitFunction(elsewhere)", 140, 60, "other")]
    g = scopes.gaps(ops, 0, 210, host, harness.HOST_SPANS)
    assert sorted(g) == sorted([
        # 10-70: the innermost event over more than half of it
        ("step_dispatch/$array.py:337 __getitem__", 60),
        # 80-130: no inner event covers more than half: the span alone
        ("step_dispatch", 50),
        # 140-200: an event of another thread does not name it
        ("loss_readback", 60)])


def test_a_device_with_no_op_in_the_window_is_an_error():
    with pytest.raises(ValueError):
        scopes.summarize(read(OPS), 1000, 2000, [0], harness.HOST_SPANS)


READERS = {"train.forward_ms": 1.0, "train.backward_ms": 2.0,
           "train.optimizer_ms": 0.4, "train.count_psum_ms": 0.25,
           "train.grad_sync_exposed_ms": 0.3, "decode.attention_ms": 0.5,
           "decode.cache_write_ms": 0.15}


def _input(summary, steps=2):
    return MetricInput(summary, Window(1.0, steps, 0, {}, {"steps": steps}),
                       {}, {}, 2)


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_scope_reader_reads_ms_per_step(name):
    # per scope, device 0 and device 1: the readers average the chips
    ns = {"jvp(train.loss)": 2e6, "transpose(jvp(train.loss))": 4e6,
          "train.optimizer": 0.8e6, "train.count_psum": 0.5e6,
          "train.grad_sync": 9e6, "decode.attention": 1e6,
          "decode.cache_write": 0.3e6}
    exposed = {"train.grad_sync": 0.6e6}
    s = scopes.Summary(1e9, [0, 1], {0: 5e8, 1: 5e8},
                       {k: {0: v * 0.5, 1: v * 1.5} for k, v in ns.items()},
                       {k: {0: v * 0.5, 1: v * 1.5}
                        for k, v in exposed.items()}, 0.0, [])
    mod = harness.load_module(harness.ROOT / "bench" / "metrics"
                              / f"{name}.py")
    assert mod.read(_input(s)) == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_scope_reader_without_its_scope_reads_none(name):
    mod = harness.load_module(harness.ROOT / "bench" / "metrics"
                              / f"{name}.py")
    empty = scopes.Summary(1e9, [0], {0: 5e8}, {}, {}, 1.0, [])
    assert mod.read(_input(empty)) is None
    assert mod.read(_input(None)) is None
    from bench import trace
    # the harness's own summary has no scope tables
    plain = trace.Summary(1e9, [0], {0: 5e8}, {0: 0.0}, {}, [])
    assert mod.read(_input(plain)) is None


def test_dispatch_lags_check_the_host_clock_against_the_device():
    ops = [Op("a", 0, 100), Op("b", 300, 100), Op("c", 700, 100),
           Op("d", 900, 50)]
    host = [HostEvent("PjitFunction(train_step)", 250, 40, "m"),
            HostEvent("PjitFunction(train_step)", 720, 40, "m")]
    # gap 100-300: dispatched at 250, device resumes 50 later; gap
    # 400-700: the next dispatch starts at 720, after the device
    # resumed: the host events sit at least 20 too late; gap 800-900
    # is too short; the window's ends are no gap a dispatch ends
    assert scopes.dispatch_lags(ops, 0, 1000, host, min_ns=150) == [50, -20]
