"""Subprocess check: the device-side PallasTransport (whole schedule as
ONE kernel, core.pallas_lowering) inside real shard_map on 8 host
devices — bit-exact vs ShardMapTransport and the numpy expectation for
every dense collective, the neighbor plan, the pipelined overlap path,
and the fused allreduce->rmsnorm epilogue.

Run via tests/test_shardmap.py (needs its own process: jax device count
is locked at first init)."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import api
from repro import compat

N = 8
MESHES = {
    "flat": (compat.make_mesh((8,), ("data",)), ("data",)),
    "pods": (compat.make_mesh((2, 4), ("pod", "data")), ("pod", "data")),
}
# one schedule-backed algorithm per collective keeps the interpret-mode
# kernel lowerings bounded; the full registry sweep is tier-1
# (tests/test_pallas_transport.py) against the same lowering
ALGOS = {
    "allgather": "ring",
    "allreduce": "ring_rs_ag",
    "reduce_scatter": "ring",
    "alltoall": "hierarchical",
}

rng = np.random.default_rng(0)
failures = []


def bits(x):
    return np.asarray(x).view(np.uint8).tobytes()


def run(mesh, axes, fn, x, out_spec=None):
    spec = P(tuple(axes))
    f = jax.jit(compat.shard_map(fn, mesh=mesh, in_specs=spec,
                                 out_specs=out_spec or spec,
                                 check_vma=False))
    with jax.set_mesh(mesh):
        return np.asarray(f(x))


def check_collective(mesh_name, mesh, axes, coll, algo):
    x = rng.normal(size=(N * N, 6)).astype(np.float32)
    outs = {}
    for tr in ("shardmap", "pallas"):
        fn = lambda v, tr=tr: getattr(api, f"mpix_{coll}")(
            v, axes, algorithm=algo, transport=tr)
        out_spec = P(None) if coll in ("allgather", "allreduce") else None
        outs[tr] = run(mesh, axes, fn, x, out_spec=out_spec)
    ok = bits(outs["shardmap"]) == bits(outs["pallas"])
    if coll == "allgather":
        ok = ok and np.allclose(outs["pallas"], x)
    elif coll == "allreduce":
        ok = ok and np.allclose(outs["pallas"],
                                x.reshape(N, N, 6).sum(0), atol=1e-4)
    elif coll == "reduce_scatter":
        ok = ok and np.allclose(outs["pallas"],
                                x.reshape(N, N, 6).sum(0), atol=1e-4)
    elif coll == "alltoall":
        want = x.reshape(N, N, 6).swapaxes(0, 1).reshape(N * N, 6)
        ok = ok and np.allclose(outs["pallas"], want, atol=1e-5)
    print(f"{mesh_name:5s} {coll:15s} {algo:16s} "
          f"{'ok' if ok else 'FAIL'}")
    if not ok:
        failures.append((mesh_name, coll, algo))


def check_overlap(mesh_name, mesh, axes):
    """run_chunked on the pallas transport (grid-pipelined single
    launch, then the consume-fold path) == monolithic alltoall."""
    x = rng.normal(size=(N * N * 2, 6)).astype(np.float32)  # [16,6]/rank

    def fold(v, tr):
        def consume(carry, chunk, i):
            return carry + chunk.sum(0)
        init = jnp.zeros((6,), jnp.float32)
        return api.mpix_alltoall_overlap(
            v, axes, consume, init, chunks=2, algorithm="pairwise",
            transport=tr)

    def mono(v):
        return api.mpix_alltoall(v, axes, algorithm="pairwise").sum(0)

    a = run(mesh, axes, lambda v: fold(v, "shardmap"), x)
    b = run(mesh, axes, lambda v: fold(v, "pallas"), x)
    c = run(mesh, axes, mono, x)
    ok = (np.allclose(a, b, atol=1e-6)
          and np.allclose(b, c, atol=1e-5))
    print(f"{mesh_name:5s} alltoall_overlap chunked          "
          f"{'ok' if ok else 'FAIL'}")
    if not ok:
        failures.append((mesh_name, "alltoall_overlap"))


def check_neighbor(mesh_name, mesh, axes, rpp):
    from repro.core.plan import CommGraph, build_plan
    from repro.core.topology import Topology

    topo = Topology(nranks=N, ranks_per_pod=rpp)
    graph = CommGraph.random(N, n_local=6, degree=5,
                             rng=np.random.default_rng(42), dup_frac=0.8)
    plan = build_plan(graph, topo, aggregate=True)
    x = rng.normal(size=(N * 6, 3)).astype(np.float32)
    fn = lambda v, tr: api.mpix_neighbor_alltoallv(v, axes, plan,
                                                   transport=tr)
    a = run(mesh, axes, lambda v: fn(v, "shardmap"), x)
    b = run(mesh, axes, lambda v: fn(v, "pallas"), x)
    ok = bits(a) == bits(b)
    print(f"{mesh_name:5s} neighbor_alltoallv aggregate      "
          f"{'ok' if ok else 'FAIL'}")
    if not ok:
        failures.append((mesh_name, "neighbor"))


def check_rmsnorm_fused(mesh_name, mesh, axes):
    """mpix_allreduce_rmsnorm: fused epilogue (pallas) vs unfused
    allreduce-then-normalize (shardmap) — same math, float tolerance
    (the fused sum order differs from the ring reduction's)."""
    d = 64
    x = rng.normal(size=(N * 4, d)).astype(np.float32)
    scale = rng.normal(size=(d,)).astype(np.float32)
    fn = lambda v, tr: api.mpix_allreduce_rmsnorm(
        v, axes, jnp.asarray(scale), algorithm="ring_rs_ag", transport=tr)
    fused = run(mesh, axes, lambda v: fn(v, "pallas"), x,
                out_spec=P(None))
    unfused = run(mesh, axes, lambda v: fn(v, "shardmap"), x,
                  out_spec=P(None))
    ok = np.allclose(fused, unfused, atol=1e-4)
    print(f"{mesh_name:5s} allreduce_rmsnorm fused           "
          f"{'ok' if ok else 'FAIL'}")
    if not ok:
        failures.append((mesh_name, "allreduce_rmsnorm"))


for mesh_name, (mesh, axes) in MESHES.items():
    for coll, algo in ALGOS.items():
        check_collective(mesh_name, mesh, axes, coll, algo)
    check_overlap(mesh_name, mesh, axes)
    check_neighbor(mesh_name, mesh, axes, 8 if mesh_name == "flat" else 4)
    check_rmsnorm_fused(mesh_name, mesh, axes)

if failures:
    raise SystemExit(f"FAILURES: {failures}")
print("ALL OK")
