"""Subprocess check: the persistent-executor cache on the shard_map path.

Proves the MPI-4 persistent-collective property on 8 forced host
devices:

  1. one jit trace per (schedule, shape, dtype) — repeated calls to a
     jitted collective never re-lower the compiled rounds (the
     ``CompiledExec.trace_count`` counter stays at 1), while a new
     dtype or slot shape lowers exactly once more;
  2. the mpix_* API path shares that executor (same cache entry, no
     per-call recompilation);
  3. the fused lowering is bit-exact with the unfused reference on a
     multi-pod staged neighbor plan that actually loses rounds to
     fusion (the alpha-term win is real, not a no-op pass);
  4. flipping REPRO_VALIDATE_SCHEDULES or the schedule fingerprint
     yields a different executor (cache invalidation).

Run via tests/test_shardmap.py (needs its own process: jax device count
is locked at first init).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("REPRO_VALIDATE_SCHEDULES", "1")

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import api, executor
from repro.core.algorithms import REGISTRY
from repro.core.plan import CommGraph, build_plan, run_shardmap, run_sim
from repro.core.topology import Topology, flat_topology
from repro.core.transport import ShardMapTransport, SimTransport

N = 8
mesh = compat.make_mesh((N,), ("data",))
topo = flat_topology(N)

# --- 1. one trace per (schedule, shape, dtype) -----------------------------
sched = REGISTRY["allgather"]["ring"](topo)
ex = executor.get_executor(sched)
tr = ShardMapTransport(N, ("data",))
f = jax.jit(compat.shard_map(
    lambda b: tr.run(sched, b), mesh=mesh,
    in_specs=P("data"), out_specs=P("data"), check_vma=False))

x32 = np.random.default_rng(0).normal(
    size=(N * sched.num_slots, 4)).astype(np.float32)
with jax.set_mesh(mesh):
    for _ in range(4):
        jax.block_until_ready(f(x32))
assert ex.trace_count == 1, f"expected 1 trace after 4 calls, got {ex.trace_count}"

with jax.set_mesh(mesh):                       # new dtype: one more trace
    for _ in range(3):
        jax.block_until_ready(f(x32.astype(jnp.bfloat16)))
assert ex.trace_count == 2, ex.trace_count

x_wide = np.random.default_rng(1).normal(
    size=(N * sched.num_slots, 6)).astype(np.float32)
with jax.set_mesh(mesh):                       # new slot shape: one more
    jax.block_until_ready(f(x_wide))
    jax.block_until_ready(f(x_wide))
assert ex.trace_count == 3, ex.trace_count
print(f"trace counts ok: 9 calls -> {ex.trace_count} traces "
      f"(1 per shape/dtype)")

# --- 1b. topology-armed executor: baked where-masks add no retraces --------
# the armed compilation bakes scratch-safe indices AND jnp.where masks
# as numpy tables (executor._ExecRound.tables); repeated jitted
# calls of the armed executor must still lower exactly once, and the
# armed executor is a distinct cache entry from the topology-free one
topo2 = Topology(8, 4)
ex_armed = executor.get_executor(sched, topo=topo2)
assert ex_armed is not ex, "topology must key a distinct cache entry"
assert executor.get_executor(sched, topo=topo2) is ex_armed
tr_armed = ShardMapTransport(N, ("data",), topo=topo2)
fa = jax.jit(compat.shard_map(
    lambda b: tr_armed.run(sched, b), mesh=mesh,
    in_specs=P("data"), out_specs=P("data"), check_vma=False))
with jax.set_mesh(mesh):
    for _ in range(5):
        jax.block_until_ready(fa(x32))
assert ex_armed.trace_count == 1, (
    f"baked masks must not retrace: 5 calls -> {ex_armed.trace_count}")
want = SimTransport(N).run_reference(
    sched, x32.reshape(N, sched.num_slots, 4))
with jax.set_mesh(mesh):
    got = np.asarray(fa(x32))
assert np.array_equal(want.reshape(got.shape), got)
# the mask/index tables are materialized once and reused
tables0 = [r.tables() for r in ex_armed._rounds]
tables1 = [r.tables() for r in ex_armed._rounds]
assert all(a is b for ta, tb in zip(tables0, tables1)
           for a, b in zip(ta, tb)), "tables/masks must bake once"
print(f"armed executor: 5 calls -> {ex_armed.trace_count} trace, "
      f"distinct cache entry, masks baked once, bit-exact")

# --- 2. the mpix_* API path shares the executor cache ----------------------
# the api path arms the executor with its own (flat, from the mesh
# axes) topology — one cache entry per geometry, reused across calls
g = jax.jit(compat.shard_map(
    lambda v: api.mpix_allgather(v, "data", algorithm="ring"),
    mesh=mesh, in_specs=P("data"), out_specs=P(None), check_vma=False))
xs = np.random.default_rng(2).normal(size=(N * 4, 3)).astype(np.float32)
with jax.set_mesh(mesh):
    for _ in range(3):
        jax.block_until_ready(g(xs))
stats = executor.cache_stats()
flat_fp = topo.fingerprint()
ring_execs = [e for e in stats["executors"]
              if e["name"] == "allgather.ring" and e["optimize"]
              and e["topology"] == flat_fp]
assert len(ring_execs) == 1, (
    f"api path must reuse one cached flat-armed allgather.ring "
    f"executor, found {len(ring_execs)}")
assert ring_execs[0]["trace_count"] == 1, ring_execs
print(f"api path shares per-geometry executor: cache size "
      f"{stats['size']}, hits {stats['hits']}")

# --- 3. fused lowering bit-exact where fusion cuts rounds ------------------
# a multi-pod staged schedule with serialized per-pod stages (what a
# naive staged builder emits; the registered builders parallel_fuse at
# plan time) must fuse 2*(R-1) -> R-1 rounds and stay bit-exact through
# the real shard_map path
from repro.core.algorithms.staged import serialized_pod_allgather

naive = serialized_pod_allgather(Topology(8, 4))
nex = executor.get_executor(naive)
assert nex.rounds_before == 6 and nex.rounds_after == 3, (
    "staged multi-pod schedule must lose rounds to fusion",
    nex.rounds_before, nex.rounds_after)
rng = np.random.default_rng(0)
xbuf = rng.normal(size=(N, N, 2)).astype(np.float32)
want_naive = SimTransport(N).run_reference(naive, xbuf)
tr_n = ShardMapTransport(N, ("data",))
fn = jax.jit(compat.shard_map(
    lambda b: tr_n.run(naive, b), mesh=mesh,
    in_specs=P("data"), out_specs=P("data"), check_vma=False))
with jax.set_mesh(mesh):
    got_naive = np.asarray(fn(xbuf.reshape(N * N, 2)))
assert np.array_equal(want_naive.reshape(got_naive.shape), got_naive)
print(f"fusion win on staged multi-pod schedule: "
      f"{nex.rounds_before} -> {nex.rounds_after} rounds, bit-exact on "
      f"shard_map")

# real colored neighbor plans: the drain pass may only ever delete
# rounds (never redistribute), must never raise the modeled time, and
# stays bit-exact
mp12 = Topology(12, 3)
graph = CommGraph.random(12, n_local=6, degree=4, rng=rng, dup_frac=0.8)
plan = build_plan(graph, mp12, aggregate=True)
pex = executor.get_executor(plan.schedule)
assert pex.rounds_after <= pex.rounds_before, (
    pex.rounds_before, pex.rounds_after)
assert (pex.compiled_schedule.modeled_time(mp12, 4096)
        <= plan.schedule.modeled_time(mp12, 4096) * 1.0001)
values = [rng.normal(size=(6, 2)).astype(np.float32) for _ in range(12)]
got = run_sim(plan, values)
for r in range(12):
    segs = [values[s][idx] for s, idx in graph.recv_layout(r)]
    want = np.concatenate(segs) if segs else np.zeros((0, 2), np.float32)
    np.testing.assert_allclose(got[r], want)
print(f"colored neighbor plan: {pex.rounds_before} -> "
      f"{pex.rounds_after} rounds, modeled time not raised, bit-exact")

# an 8-rank neighbor plan through the real shard_map path, fused vs
# unfused reference
graph8 = CommGraph.random(N, n_local=5, degree=4, rng=rng, dup_frac=0.8)
plan8 = build_plan(graph8, Topology(8, 4), aggregate=True)
n_local_max = max(graph8.local_sizes)
vals = [rng.normal(size=(n_local_max, 2)).astype(np.float32)
        for _ in range(N)]
want8 = run_sim(plan8, vals)
h = jax.jit(compat.shard_map(
    lambda v: run_shardmap(plan8, v, ("data",)), mesh=mesh,
    in_specs=P("data"), out_specs=P("data"), check_vma=False))
with jax.set_mesh(mesh):
    got8 = np.asarray(h(np.concatenate(vals, axis=0)))
got8 = got8.reshape(N, -1, 2)
for r in range(N):
    np.testing.assert_allclose(got8[r, : plan8.recv_sizes[r]], want8[r])
print("neighbor plan shard_map fused execution ok")

# --- 4. cache invalidation -------------------------------------------------
before = executor.get_executor(sched)
os.environ["REPRO_VALIDATE_SCHEDULES"] = "0"
after = executor.get_executor(sched)
assert after is not before, "validation-flag flip must invalidate"
os.environ["REPRO_VALIDATE_SCHEDULES"] = "1"
assert executor.get_executor(sched) is before
other = REGISTRY["allgather"]["bruck"](topo)
assert other.fingerprint() != sched.fingerprint()
assert executor.get_executor(other) is not before
print("cache invalidation ok (env flag + fingerprint)")

print("ALL OK")
