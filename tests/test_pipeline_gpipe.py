"""core/pipeline.py unit + property tests (single device).

The multi-device gpipe forward/AD equivalence runs from
``tests/device_scripts/check_partitioned.py``; here we cover the
degenerate 1-stage pipeline against a sequential oracle, the
stage->layer partition properties, and the GPipe wavefront expressed
in the shared ``CommSchedule``/``ComputeEvent`` vocabulary — the
generic makespan pass must reproduce the classic pipeline cost with no
GPipe-specific pricing.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except Exception:                                  # pragma: no cover
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).parent))
    from _hypothesis_stub import given, settings, st

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import executor, pipeline as pl
from repro.core.topology import flat_topology


@pytest.fixture(autouse=True)
def _fresh_executor_cache():
    executor.clear_cache()
    yield
    executor.clear_cache()


def test_gpipe_single_stage_matches_sequential():
    """S=1 degenerates to a per-microbatch map: same numbers as calling
    the stage directly (pipelined == unpipelined oracle)."""
    mesh = compat.make_mesh((1,), ("data",))
    rng = np.random.default_rng(0)
    W = rng.normal(size=(5, 5)).astype(np.float32) * 0.3
    b = rng.normal(size=(5,)).astype(np.float32)
    xs = rng.normal(size=(6, 4, 5)).astype(np.float32)

    def stage_fn(p, h):
        return jnp.tanh(h @ p[0] + p[1])

    from jax.sharding import PartitionSpec as P
    f = jax.jit(compat.shard_map(
        lambda v: pl.gpipe(stage_fn, (W, b), v, "data"),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    with jax.set_mesh(mesh):
        got = np.asarray(f(xs))
    want = np.tanh(xs @ W + b)
    np.testing.assert_allclose(got, want, atol=1e-5)


@settings(max_examples=40, deadline=None)
@given(n_layers=st.integers(1, 64), n_stages=st.integers(1, 16))
def test_stage_params_spec_properties(n_layers, n_stages):
    if n_stages > n_layers:
        n_stages = n_layers
    spans = pl.stage_params_spec(n_layers, n_stages)
    assert len(spans) == n_stages
    # contiguous partition of [0, n_layers)
    flat = [i for r in spans for i in r]
    assert flat == list(range(n_layers))
    sizes = [len(r) for r in spans]
    assert max(sizes) - min(sizes) <= 1
    # the remainder lands on the LAST stages (they also hold the head)
    assert sizes == sorted(sizes)


def test_gpipe_wavefront_schedule_shape():
    M, S = 6, 4
    sched = pl.gpipe_wavefront_schedule(M, S, 1e-3)
    T = M + S - 1
    assert len(sched.rounds) == T
    assert len(sched.compute_events) == T
    assert all(ev.seconds == 1e-3 and ev.after_round == t
               for t, ev in enumerate(sched.compute_events))
    with pytest.raises(ValueError):
        pl.gpipe_wavefront_schedule(0, 4, 1e-3)
    with pytest.raises(ValueError):
        pl.gpipe_wavefront_schedule(4, 0, 1e-3)


def test_gpipe_wavefront_makespan_is_pipelined():
    """The generic pass prices the wavefront like a software pipeline:
    tick t's compute overlaps shift t+1 (consecutive shifts are RAW on
    the in-flight slot, so rounds stay serialized; events slide one
    group right).  Strictly better than the serial sum, and >= the
    trivial lower bound max(total shift, total compute)."""
    M, S = 8, 4
    topo = flat_topology(S)
    tick_s = 1e-3
    sched = pl.gpipe_wavefront_schedule(M, S, tick_s)
    ex = executor.get_executor(sched, topo=topo)
    T = M + S - 1
    slot = float(1 << 16)
    shift = ex.compiled_schedule.modeled_time(topo, slot) / len(
        ex.compiled_schedule.rounds)
    mk = ex.makespan(slot)
    serial = T * (shift + tick_s)
    assert mk <= serial * (1 + 1e-9)
    assert mk < serial * (1 - 1e-3)            # real overlap
    assert mk >= max(T * shift, T * tick_s) * (1 - 1e-9)
    # classic pipeline cost: first shift exposed, then max(shift, tick)
    # per remaining tick, then the last tick's compute exposed
    want = shift + (T - 1) * max(shift, tick_s) + tick_s
    assert mk == pytest.approx(want, rel=1e-6)
