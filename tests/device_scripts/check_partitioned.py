"""Subprocess check: partitioned-communication primitives (paper §2.3)
and the gpipe pipeline, on 8 host devices.

Paper claim 1 ("with only one partition, MPIPCL is no worse than base
point-to-point") is checked structurally: the 1-partition pipeline is
the monolithic transfer (same single collective in the HLO) and all
partition counts are bit-identical in value.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import re
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.algorithms import partitioned as pc
from repro.core import pipeline as pl
from repro import compat

N = 8
mesh = compat.make_mesh((N,), ("data",))
rng = np.random.default_rng(0)
failures = []


def check(name, ok):
    print(f"{name:45s} {'ok' if ok else 'FAIL'}")
    if not ok:
        failures.append(name)


# -- partitioned ppermute: all partition counts == monolithic -------------
x = rng.normal(size=(N * 16, 4)).astype(np.float32)
perm = [(i, (i + 1) % N) for i in range(N)]
outs = {}
for parts in (1, 2, 4, 8):
    f = jax.jit(compat.shard_map(
        lambda v, p=parts: pc.partitioned_ppermute(v, "data", perm, p),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))
    with jax.set_mesh(mesh):
        outs[parts] = np.asarray(f(x))
want = x.reshape(N, 16, 4)[np.array([(i - 1) % N for i in range(N)])]
check("partitioned_ppermute matches shift", np.allclose(
    outs[1], want.reshape(N * 16, 4)))
for parts in (2, 4, 8):
    check(f"partitions={parts} bit-identical to 1",
          np.array_equal(outs[parts], outs[1]))

# claim 1 structural check: the 1-partition pipeline lowers to the same
# number of collective-permute ops as the monolithic ppermute
def _n_cp(fn):
    with jax.set_mesh(mesh):
        hlo = jax.jit(fn).lower(x).compile().as_text()
    return len(re.findall(r"= \S* ?collective-permute", hlo))


f1 = compat.shard_map(lambda v: pc.partitioned_ppermute(v, "data", perm, 1),
                   mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                   check_vma=False)
f0 = compat.shard_map(lambda v: jax.lax.ppermute(v, "data", perm),
                   mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                   check_vma=False)
check("1-partition == monolithic collective count", _n_cp(f1) == _n_cp(f0))

# -- early-bird consume: running sum over arriving partitions -------------
f = jax.jit(compat.shard_map(
    lambda v: pc.partitioned_ppermute(
        v, "data", perm, 4,
        consume=lambda c, chunk: c + chunk.sum(0),
        init=jnp.zeros((4,), jnp.float32)),
    mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False))
with jax.set_mesh(mesh):
    got = np.asarray(f(x))
check("early-bird consume == sum of received shard",
      np.allclose(got.reshape(N, 4), want.sum(1), atol=1e-4))

# -- allgather_matmul ------------------------------------------------------
xg = rng.normal(size=(N * 8, 16)).astype(np.float32)
w = rng.normal(size=(16, 12)).astype(np.float32)
f = jax.jit(compat.shard_map(
    lambda v, ww: pc.allgather_matmul(v, ww, "data"),
    mesh=mesh, in_specs=(P("data"), P()), out_specs=P(),
    check_vma=False))
with jax.set_mesh(mesh):
    got = np.asarray(f(xg, w))
check("allgather_matmul == all_gather(x) @ w",
      np.allclose(got, xg @ w, atol=1e-4))

# -- matmul_reduce_scatter -------------------------------------------------
xr = rng.normal(size=(N * 4, N * 16)).astype(np.float32)   # m=32, k=128
wr = rng.normal(size=(N * 16, 10)).astype(np.float32)
f = jax.jit(compat.shard_map(
    lambda v, ww: pc.matmul_reduce_scatter(v, ww, "data"),
    mesh=mesh,
    in_specs=(P(None, "data"), P("data")), out_specs=P("data"),
    check_vma=False))
# inside: each rank has x_local [m, k/N] and w_local [k/N, 10]
with jax.set_mesh(mesh):
    got = np.asarray(f(xr, wr))       # [m, 10] scattered over ranks
check("matmul_reduce_scatter == psum_scatter(x @ w)",
      np.allclose(got, xr @ wr, atol=1e-3))

# -- bucketed psum ----------------------------------------------------------
tree = {"a": rng.normal(size=(N, 33)).astype(np.float32),
        "b": rng.normal(size=(N, 5, 7)).astype(np.float32)}
f = jax.jit(compat.shard_map(
    lambda t: pc.bucketed_psum(t, "data", buckets=3),
    mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False))
with jax.set_mesh(mesh):
    got = f(tree)
check("bucketed_psum == tree psum",
      np.allclose(got["a"], tree["a"].sum(0, keepdims=True), atol=1e-4)
      and np.allclose(got["b"], tree["b"].sum(0, keepdims=True), atol=1e-4))

# -- gpipe: S stages of affine layers == sequential oracle ----------------
S, M, UB, D = 8, 6, 4, 5
Ws = rng.normal(size=(S, D, D)).astype(np.float32) * 0.3
bs = rng.normal(size=(S, D)).astype(np.float32)
xs = rng.normal(size=(M, UB, D)).astype(np.float32)


def stage_fn(p, h):
    W, b = p
    return jnp.tanh(h @ W + b)


f = jax.jit(compat.shard_map(
    lambda W, b, v: pl.gpipe(stage_fn, (W[0], b[0]), v, "data",
                             return_to_first=True),
    mesh=mesh, in_specs=(P("data"), P("data"), P()),
    out_specs=P(), check_vma=False))
with jax.set_mesh(mesh):
    got = np.asarray(f(Ws, bs, xs))
h = xs
for s in range(S):
    h = np.tanh(h @ Ws[s] + bs[s])
# output lands on stage 0's copy after return_to_first
check("gpipe forward == sequential stages", np.allclose(got, h, atol=1e-4))

# gpipe differentiability: grad of sum(out) wrt input matches oracle


def loss_pipe(v):
    out = compat.shard_map(
        lambda W, b, vv: pl.gpipe(stage_fn, (W[0], b[0]), vv, "data",
                                  return_to_first=True),
        mesh=mesh, in_specs=(P("data"), P("data"), P()),
        out_specs=P(), check_vma=False)(Ws, bs, v)
    return out.sum()


def loss_seq(v):
    h = v
    for s in range(S):
        h = jnp.tanh(h @ Ws[s] + bs[s])
    return h.sum()


with jax.set_mesh(mesh):
    g_pipe = np.asarray(jax.jit(jax.grad(loss_pipe))(xs))
g_seq = np.asarray(jax.grad(loss_seq)(xs))
check("gpipe reverse-mode AD == sequential grad",
      np.allclose(g_pipe, g_seq, atol=1e-4))

if failures:
    raise SystemExit(f"FAILURES: {failures}")
print("ALL OK")
