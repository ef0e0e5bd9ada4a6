"""Subprocess check: SimTransport and ShardMapTransport are bit-exact on
the unified IR — every registered schedule (dense families incl. the
staged builders + partitioned chunked shifts) and both neighborhood
plan modes, executed on the same random buffer by both backends, for
every topology in {flat, 2-pod, 2x4 torus, 3-level 2x(2x2)} x dtype in
{float32, bfloat16}.

This is the executor-equivalence half of the unification contract: one
IR, two backends, zero semantic drift.  (Semantic correctness of each
algorithm against its oracle lives in test_algorithms_sim /
test_neighbor_plan; the shard_map API path in check_shardmap_transport.)
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("REPRO_VALIDATE_SCHEDULES", "1")

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.algorithms import REGISTRY
from repro.core.plan import CommGraph, build_plan
from repro.core.schedule import NotApplicable
from repro.core.topology import Topology, flat_topology, torus_topology
from repro.core.transport import ShardMapTransport, SimTransport

N, FEAT = 8, 2
CASES = {
    "flat":  (flat_topology(N), (N,), ("r",)),
    "pods":  (Topology(N, 4), (2, 4), ("pod", "data")),
    "torus": (torus_topology(1, 2, 4), (2, 4), ("y", "x")),
    # 3-level: DCN over a 2x2 torus (the staged builders' home turf;
    # the full 2x(4x2) sweep runs in check_hierarchical.py)
    "3lvl":  (torus_topology(2, 2, 2), (2, 2, 2), ("pod", "y", "x")),
}
DTYPES = {"float32": np.float32, "bfloat16": jnp.bfloat16}

rng = np.random.default_rng(0)
failures = []
checked = 0


def bit_exact(sched, mesh, axes, dtype) -> bool:
    x = rng.normal(size=(N, sched.num_slots, FEAT)).astype(dtype)
    # the oracle is the UNFUSED rank-by-rank reference loop, so this
    # sweep proves the compiled/fused ppermute lowering (and the
    # vectorized simulator, via test_executor.py) against pre-executor
    # semantics — not merely the two compiled backends against each other
    want = SimTransport(N).run_reference(sched, x)
    assert np.array_equal(want, SimTransport(N).run(sched, x))
    tr = ShardMapTransport(N, axes)
    f = jax.jit(compat.shard_map(
        lambda b: tr.run(sched, b), mesh=mesh,
        in_specs=P(axes), out_specs=P(axes), check_vma=False))
    with jax.set_mesh(mesh):
        got = np.asarray(f(x.reshape(N * sched.num_slots, FEAT)))
    return np.array_equal(want.reshape(got.shape), got)


for case, (topo, mesh_shape, axes) in CASES.items():
    mesh = compat.make_mesh(mesh_shape, axes)
    schedules = []
    for coll, algos in REGISTRY.items():
        for name, builder in algos.items():
            try:
                schedules.append((f"{coll}.{name}", builder(topo)))
            except NotApplicable:      # e.g. pow2-only on this topo
                continue
    graph = CommGraph.random(N, n_local=6, degree=4, rng=rng,
                             dup_frac=0.8)
    for aggregate in (False, True):
        plan = build_plan(graph, topo, aggregate=aggregate)
        schedules.append((plan.name, plan.schedule))
    for dt_name, dtype in DTYPES.items():
        for label, sched in schedules:
            ok = bit_exact(sched, mesh, axes, dtype)
            checked += 1
            if not ok:
                failures.append((case, label, dt_name))
                print(f"{case:5s} {dt_name:8s} {label:40s} FAIL")
    print(f"{case:5s} {len(schedules)} schedules x {len(DTYPES)} dtypes ok")

if failures:
    raise SystemExit(f"FAILURES: {failures}")
print(f"checked {checked} (schedule, topology, dtype) cases")
print("ALL OK")
