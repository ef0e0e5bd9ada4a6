"""Subprocess check: ShardMapTransport (ppermute execution) matches the
numpy semantics on 8 host devices, for every collective x algorithm,
single- and multi-pod, including the full mpix_* API and the xla
substrate path.

Run via tests/test_shardmap.py (needs its own process: jax device count is
locked at first init)."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
from jax.sharding import PartitionSpec as P

from repro.core import api
from repro import compat

N = 8
MESHES = {
    "flat": (compat.make_mesh((8,), ("data",)),
             ("data",)),
    "pods": (compat.make_mesh((2, 4), ("pod", "data")),
             ("pod", "data")),
}

ALGOS = {
    "allgather": ["xla", "ring", "bruck", "recursive_doubling",
                  "hierarchical", "staged"],
    "allreduce": ["xla", "ring_rs_ag", "recursive_halving_doubling",
                  "hierarchical", "staged"],
    "reduce_scatter": ["xla", "ring", "recursive_halving", "hierarchical",
                       "staged"],
    "alltoall": ["xla", "pairwise", "bruck", "hierarchical", "staged"],
}

rng = np.random.default_rng(0)
failures = []


def check(mesh_name, mesh, axes, coll, algo):
    spec = P(tuple(axes))
    if coll == "allgather":
        x = rng.normal(size=(N * 4, 6)).astype(np.float32)
        f = jax.jit(compat.shard_map(
            lambda v: api.mpix_allgather(v, axes, algorithm=algo),
            mesh=mesh, in_specs=spec, out_specs=P(None), check_vma=False))
        with jax.set_mesh(mesh):
            got = np.asarray(f(x))
        return np.allclose(got, x)
    if coll == "allreduce":
        x = rng.normal(size=(N * 4, 6)).astype(np.float32)
        f = jax.jit(compat.shard_map(
            lambda v: api.mpix_allreduce(v, axes, algorithm=algo),
            mesh=mesh, in_specs=spec, out_specs=P(None), check_vma=False))
        with jax.set_mesh(mesh):
            got = np.asarray(f(x))
        return np.allclose(got, x.reshape(N, 4, 6).sum(0), atol=1e-4)
    if coll == "reduce_scatter":
        # distinct per-rank contributions: feed a sharded [N*N, 6] whose
        # rank-r shard is that rank's full N-row contribution
        x = rng.normal(size=(N * N, 6)).astype(np.float32)
        f = jax.jit(compat.shard_map(
            lambda v: api.mpix_reduce_scatter(v, axes, algorithm=algo),
            mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
        with jax.set_mesh(mesh):
            got = np.asarray(f(x))  # rank r returns reduced row r -> [N, 6]
        want = x.reshape(N, N, 6).sum(0)  # row r fully reduced
        return np.allclose(got, want, atol=1e-4)
    if coll == "alltoall":
        x = rng.normal(size=(N * N, 6)).astype(np.float32)
        f = jax.jit(compat.shard_map(
            lambda v: api.mpix_alltoall(v, axes, algorithm=algo),
            mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
        with jax.set_mesh(mesh):
            got = np.asarray(f(x))
        want = x.reshape(N, N, 6).swapaxes(0, 1).reshape(N * N, 6)
        return np.allclose(got, want, atol=1e-5)
    raise ValueError(coll)


for mesh_name, (mesh, axes) in MESHES.items():
    for coll, algos in ALGOS.items():
        for algo in algos:
            ok = check(mesh_name, mesh, axes, coll, algo)
            if not ok:
                failures.append((mesh_name, coll, algo))
            print(f"{mesh_name:5s} {coll:15s} {algo:28s} "
                  f"{'ok' if ok else 'FAIL'}")

# mpix_allreduce zero pads a leading dim that N does not divide and cuts
# its chunks along it: bitwise the run_reference of that layout
from repro.core.algorithms import REGISTRY
from repro.core.topology import Topology
from repro.core.transport import SimTransport

mesh, axes = MESHES["flat"]
topo = Topology(N, N)
for algo in ("ring_rs_ag", "recursive_halving_doubling"):
    sched = REGISTRY["allreduce"][algo](topo)
    for shape in ((13,), (3, 5), (5, 2, 3)):
        xr = rng.normal(size=(N,) + shape).astype(np.float32)
        f = jax.jit(compat.shard_map(
            lambda v, a=algo: api.mpix_allreduce(v, axes, algorithm=a,
                                                 topo=topo),
            mesh=mesh, in_specs=P(axes), out_specs=P(axes)))
        with jax.set_mesh(mesh):
            got = np.asarray(f(xr.reshape((N * shape[0],) + shape[1:])))
        lead = -(-shape[0] // N) * N
        pad = np.zeros((N, lead) + shape[1:], np.float32)
        pad[:, : shape[0]] = xr
        ref = SimTransport(N).run_reference(
            sched, pad.reshape((N, N, -1) + shape[1:]))
        want = ref.reshape((N, lead) + shape[1:])[:, : shape[0]]
        ok = (np.array_equal(got.reshape(want.shape).view(np.uint32),
                             want.view(np.uint32))
              and np.allclose(want[0], xr.sum(0), atol=1e-4))
        if not ok:
            failures.append(("flat", "allreduce padded", algo, shape))
        print(f"flat  allreduce padded  {algo:28s} {shape} "
              f"{'ok' if ok else 'FAIL'}")

if failures:
    raise SystemExit(f"FAILURES: {failures}")
print("ALL OK")
