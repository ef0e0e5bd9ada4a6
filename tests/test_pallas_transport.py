"""Device-side Pallas transport conformance (core.pallas_lowering).

The contract mirrors test_executor's: the single-kernel lowering of
every registered schedule — the WHOLE compiled round sequence as ONE
``pallas_call`` — is bit-exact with the rank-by-rank oracle
``SimTransport.run_reference``, across topology classes and dtypes
(float32 everywhere; bfloat16 on the flat topology, compared through a
uint8 view so -0.0/NaN payloads cannot hide).  On top of that:

  * launch amortization — R compiled rounds cost exactly ONE launch per
    ``run`` (``PallasExec.launches``), and the jit cache keeps it at one
    trace per (shape, dtype, chunks) — the persistent-collective
    property;
  * grid chunking (``chunks > 1`` = double-buffered block pipeline) is
    bit-identical to the monolithic launch;
  * the ``transport=`` plumbing in ``core.api`` rejects unknown names
    with the valid choices in the message, and the tuner's transport
    policy cell prices shardmap-vs-pallas per size bucket;
  * the compute-fused terminal rounds — the rmsnorm allreduce epilogue
    and the attention dispatch-gather prologue — match their jnp
    oracles (and the plain kernels where they degenerate to them).

The multi-device half (PallasTransport inside shard_map vs
ShardMapTransport, the fused ``mpix_allreduce_rmsnorm``) runs on forced
host devices in tests/device_scripts/check_pallas_transport.py via
test_shardmap.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import api as mpix
from repro.core import executor, pallas_lowering, tuner
from repro.core.algorithms import REGISTRY
from repro.core.pallas_lowering import get_pallas_exec
from repro.core.schedule import NotApplicable
from repro.core.topology import Topology, flat_topology, torus_topology
from repro.core.transport import (PallasTransport, SimTransport,
                                  TransportError)


@pytest.fixture(autouse=True)
def _fresh_caches():
    executor.clear_cache()
    pallas_lowering.clear_cache()
    yield
    executor.clear_cache()
    pallas_lowering.clear_cache()


TOPOS = {
    "flat": flat_topology(8),
    "2pod": Topology(8, 4),
    "3lvl": torus_topology(2, 2, 2),
}


def _registry_schedules(topo):
    out = []
    for coll, algos in REGISTRY.items():
        for name, builder in algos.items():
            try:
                out.append((f"{coll}.{name}", builder(topo)))
            except NotApplicable:
                continue
    return out


def _bits(x):
    return np.asarray(x).view(np.uint8)


# ---------------------------------------------------------------------------
# bit-exactness: one kernel == rank-by-rank oracle (registry sweep)
# ---------------------------------------------------------------------------


# bf16 only on the flat topology: the sweep pays a real interpret-mode
# lowering per (schedule, dtype) and the routing program is dtype-
# independent — flat8 bf16 already pins the -0.0/rounding behavior.
SWEEPS = [("flat", np.float32), ("2pod", np.float32),
          ("3lvl", np.float32), ("flat", jnp.bfloat16)]


@pytest.mark.parametrize(
    "topo_name,dtype", SWEEPS,
    ids=[f"{t}-{np.dtype(d).name}" for t, d in SWEEPS])
def test_single_kernel_bit_exact_with_reference(topo_name, dtype):
    topo = TOPOS[topo_name]
    n = topo.nranks
    rng = np.random.default_rng(0)
    tr = SimTransport(n)
    pt = PallasTransport(n, topo=topo)
    seen = set()
    for label, sched in _registry_schedules(topo):
        if sched.fingerprint() in seen:     # one lowering per content
            continue
        seen.add(sched.fingerprint())
        buf = rng.integers(-8, 8, (n, sched.num_slots, 2)).astype(dtype)
        want = tr.run_reference(sched, buf)
        pex = get_pallas_exec(sched, topo=topo)
        got = pex.run(buf)
        assert _bits(want).tobytes() == _bits(got).tobytes(), (
            topo_name, label, np.dtype(dtype).name)
        # the transport wrapper is the same lowering
        got_tr = pt.run_global(sched, buf)
        assert _bits(want).tobytes() == _bits(got_tr).tobytes(), label


def test_r_rounds_cost_one_launch_and_one_trace():
    """The amortization the whole module exists for: a 14-round
    schedule runs as ONE pallas_call per invocation, and repeated runs
    reuse the jitted lowering (trace count stays 1)."""
    topo = TOPOS["flat"]
    sched = REGISTRY["allreduce"]["ring_rs_ag"](topo)
    pex = get_pallas_exec(sched, topo=topo)
    assert pex.rounds > 1                       # R genuinely > 1
    rng = np.random.default_rng(1)
    buf = rng.normal(size=(8, sched.num_slots, 4)).astype(np.float32)
    for i in range(3):
        pex.run(buf)
    assert pex.launches == 3                    # 1 launch per run, not R
    assert pex.jit_traces == 1                  # persistent lowering
    # the module cache hands back the same lowered object
    assert get_pallas_exec(sched, topo=topo) is pex


def test_chunked_grid_pipeline_bit_identical():
    topo = TOPOS["2pod"]
    sched = REGISTRY["alltoall"]["hierarchical"](topo)
    pex = get_pallas_exec(sched, topo=topo)
    rng = np.random.default_rng(2)
    buf = rng.normal(size=(8, sched.num_slots, 8, 3)).astype(np.float32)
    base = pex.run(buf)
    for chunks in (2, 4, 8):
        got = pex.run(buf, chunks=chunks)
        assert _bits(base).tobytes() == _bits(got).tobytes(), chunks
    with pytest.raises(ValueError, match="chunks"):
        pex.run(buf, chunks=3)                  # 8 % 3 != 0


# ---------------------------------------------------------------------------
# api plumbing + tuner transport policy
# ---------------------------------------------------------------------------


def test_unknown_transport_rejected_with_choices():
    x = jnp.zeros((8,), jnp.float32)
    with pytest.raises(ValueError, match="shardmap"):
        mpix.mpix_allgather(x, "data", transport="nvlink")
    with pytest.raises(ValueError, match="pallas"):
        mpix.mpix_alltoall(jnp.zeros((8, 2)), "data", transport="bogus")
    with pytest.raises(ValueError, match="expected one of"):
        mpix.mpix_allreduce(x, "data", transport="sharmdap")  # typo


def test_tuner_prices_transport_per_size_bucket():
    topo = TOPOS["flat"]
    table = tuner.tune_transport(topo)
    assert table, "transport cell must not be empty"
    bests = set()
    for nbytes, rec in table.items():
        assert rec["best"] in ("shardmap", "pallas"), nbytes
        assert rec["times"]["pallas"] > 0
        assert rec["times"]["shardmap"] > 0
        bests.add(rec["best"])
    # the model must produce a real crossover, not a constant answer
    assert bests == {"shardmap", "pallas"}
    # policy ladder: fixed never leaves the default substrate
    assert tuner.select_transport(topo, 4096,
                                  policy="fixed") == "shardmap"
    small = tuner.select_transport(topo, 1024, policy="model")
    large = tuner.select_transport(topo, 1 << 24, policy="model")
    assert small == "pallas" and large == "shardmap"


def test_auto_transport_resolves_to_valid_choice():
    topo = TOPOS["flat"]
    for nbytes in (256, 1 << 22):
        kind = mpix._resolve_transport("auto", topo, nbytes,
                                       policy="model")
        assert kind in ("shardmap", "pallas")


def test_pallas_past_the_vmem_bound_is_typed_and_never_auto(monkeypatch):
    """A schedule whose slot blocks cannot each hold one (8, 128) tile
    inside ``VMEM_BUDGET`` raises a ``TransportError`` naming the bound,
    and ``transport="auto"`` runs that schedule on shardmap."""
    topo = TOPOS["flat"]
    sched = REGISTRY["allgather"]["ring"](topo)
    pex = get_pallas_exec(sched, topo=topo)
    assert pex.fits
    assert mpix._resolve_transport("auto", topo, 1024, policy="model",
                                   schedule=sched) == "pallas"
    monkeypatch.setattr(pallas_lowering, "VMEM_BUDGET",
                        pex.min_vmem_bytes - 1)
    assert not pex.fits
    with pytest.raises(TransportError, match="VMEM_BUDGET"):
        pex.run(np.zeros((topo.nranks, sched.num_slots, 8), np.float32))
    assert mpix._resolve_transport("auto", topo, 1024, policy="model",
                                   schedule=sched) == "shardmap"


# ---------------------------------------------------------------------------
# compute-fused terminal rounds
# ---------------------------------------------------------------------------


def test_rmsnorm_allreduce_epilogue_matches_reference():
    from repro.kernels.rmsnorm.ops import (rmsnorm, rmsnorm_allreduce,
                                           rmsnorm_allreduce_ref)
    rng = np.random.default_rng(3)
    parts = rng.normal(size=(4, 16, 128)).astype(np.float32)
    scale = rng.normal(size=(128,)).astype(np.float32)
    want = rmsnorm_allreduce_ref(parts, scale, eps=1e-6,
                                 gemma_style=False)
    got = rmsnorm_allreduce(parts, scale)
    # fused == unfused KERNEL (sum in f32, then the same normalize
    # body) bitwise; the jnp reference agrees to rounding
    unfused = rmsnorm(jnp.sum(jnp.asarray(parts), axis=0), scale)
    assert _bits(unfused).tobytes() == _bits(got).tobytes()
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # bf16 payload + gemma style
    pb = parts.astype(jnp.bfloat16)
    got16 = rmsnorm_allreduce(pb, scale, 1e-6, True)
    want16 = rmsnorm_allreduce_ref(pb, scale, eps=1e-6, gemma_style=True)
    # the ref rounds the sum to bf16 before normalizing; the kernel
    # keeps it in f32 — compare at bf16 resolution
    assert np.allclose(np.asarray(got16, np.float32),
                       np.asarray(want16, np.float32),
                       rtol=2e-2, atol=5e-2)
    # gradients flow through the fused kernel (custom VJP vs reference)
    f = lambda p, s: jnp.sum(jnp.square(rmsnorm_allreduce(p, s)))
    g = lambda p, s: jnp.sum(jnp.square(
        rmsnorm_allreduce_ref(p, s, eps=1e-6, gemma_style=False)))
    dp, ds = jax.grad(f, argnums=(0, 1))(jnp.asarray(parts),
                                         jnp.asarray(scale))
    rp, rs = jax.grad(g, argnums=(0, 1))(jnp.asarray(parts),
                                         jnp.asarray(scale))
    assert np.allclose(np.asarray(dp), np.asarray(rp), atol=1e-4)
    assert np.allclose(np.asarray(ds), np.asarray(rs), atol=1e-4)


def test_attention_gather_prologue_matches_reference():
    from repro.kernels.attention.ops import (flash_attention,
                                             gathered_attention_ref)
    rng = np.random.default_rng(4)
    B, S, H, D = 2, 128, 4, 64
    q = rng.normal(size=(B, S, H, D)).astype(np.float32)
    k = rng.normal(size=(B, S, 2, D)).astype(np.float32)
    v = rng.normal(size=(B, S, 2, D)).astype(np.float32)
    # identity rows degenerate to the plain kernel, bitwise
    ident = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    plain = flash_attention(q, k, v, causal=True)
    fused = flash_attention(q, k, v, causal=True, q_rows=ident)
    assert _bits(plain).tobytes() == _bits(fused).tobytes()
    # random permutation with dead (-1) rows == explicit gather + ref
    rows = np.stack([rng.permutation(S) for _ in range(B)]).astype(
        np.int32)
    rows[:, ::7] = -1                          # dropped dispatch slots
    got = flash_attention(q, k, v, causal=True, q_rows=jnp.asarray(rows))
    want = gathered_attention_ref(q, k, v, jnp.asarray(rows),
                                  causal=True)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.all(np.asarray(got)[rows < 0] == 0)   # dead rows exact 0
    # grads: the gather joins the differentiated graph (scatter-add)
    f = lambda q_: jnp.sum(jnp.square(flash_attention(
        q_, k, v, causal=True, q_rows=jnp.asarray(rows))))
    g = lambda q_: jnp.sum(jnp.square(gathered_attention_ref(
        q_, k, v, jnp.asarray(rows), causal=True)))
    dq = jax.grad(f)(jnp.asarray(q))
    rq = jax.grad(g)(jnp.asarray(q))
    assert np.allclose(np.asarray(dq), np.asarray(rq), atol=2e-4)


def test_interpret_shim_env_override(monkeypatch):
    """No variable overrides the interpret decision: the interpreter
    runs exactly when no TPU backs the default backend, so a TPU run
    always compiles its kernels."""
    from repro.kernels.compat import pallas_interpret
    want = jax.default_backend() != "tpu"
    for v in ("1", "0"):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", v)
        assert pallas_interpret() is want
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    assert pallas_interpret() is want
