"""Subprocess check: NeighborPlan's shard_map executor == numpy oracle on
8 host devices, standard + locality-aware, flat + pods meshes."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
from jax.sharding import PartitionSpec as P

from repro.core.plan import CommGraph, build_plan, run_sim, run_shardmap
from repro.core.topology import Topology
from repro import compat

N, N_LOCAL, FEAT = 8, 6, 3
rng = np.random.default_rng(42)
graph = CommGraph.random(N, n_local=N_LOCAL, degree=5, rng=rng,
                         dup_frac=0.8)
values = [rng.normal(size=(N_LOCAL, FEAT)).astype(np.float32)
          for _ in range(N)]

MESHES = {
    "flat": (compat.make_mesh((8,), ("data",)), ("data",), 8),
    "pods": (compat.make_mesh((2, 4), ("pod", "data")), ("pod", "data"), 4),
}

failures = []
for mesh_name, (mesh, axes, rpp) in MESHES.items():
    topo = Topology(nranks=N, ranks_per_pod=rpp)
    for aggregate in (False, True):
        plan = build_plan(graph, topo, aggregate=aggregate)
        want = run_sim(plan, values)

        f = jax.jit(compat.shard_map(
            lambda v: run_shardmap(plan, v, axes),
            mesh=mesh, in_specs=P(tuple(axes)), out_specs=P(tuple(axes)),
            check_vma=False))
        stacked = np.stack(values).reshape((N * N_LOCAL, FEAT))
        with jax.set_mesh(mesh):
            got = np.asarray(f(stacked))
        got = got.reshape(N, -1, FEAT)
        ok = all(np.allclose(got[r, : plan.recv_sizes[r]], want[r],
                             atol=1e-6) for r in range(N))
        print(f"{mesh_name:5s} aggregate={aggregate!s:5s} "
              f"rounds={plan.num_rounds:3d} {'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append((mesh_name, aggregate))

if failures:
    raise SystemExit(f"FAILURES: {failures}")
print("ALL OK")
