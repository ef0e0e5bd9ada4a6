"""Subprocess check (8 host devices): the pipelined/overlap hot paths.

  1. ShardMapTransport.run_chunked == run (bit-identical reassembly)
     and the early-bird fold sees every chunk.
  2. mpix_alltoall_overlap == mpix_alltoall for every chunk count, xla
     and schedule-backed algorithms (the fold reproduces the monolithic
     output exactly).
  3. MoE dispatch with EPOptions.overlap_chunks in {None, 2, 4, 0/auto}
     is equivalent (pipelined == unpipelined oracle).
  4. Explicit-DP train step with overlap_grad_chunks == the unpipelined
     explicit step (same loss, same updated params, same grad norm).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat, configs
from repro.core import api as mpix
from repro.core.algorithms import REGISTRY
from repro.core.topology import flat_topology
from repro.core.transport import ShardMapTransport
from repro.data import DataPipeline, PipelineConfig
from repro.models import moe as moe_mod
from repro.train.moe_dispatch import EPOptions, make_moe_dispatch
from repro.train.step import TrainOptions, init_train_state, make_train_step

failures = []


def check(name, ok):
    print(f"{name:58s} {'ok' if ok else 'FAIL'}")
    if not ok:
        failures.append(name)


N = 8
mesh1d = compat.make_mesh((N,), ("data",))
rng = np.random.default_rng(0)

# ---------------------------------------------------------------------------
# 1. ShardMapTransport.run_chunked == run
# ---------------------------------------------------------------------------
sched = REGISTRY["alltoall"]["pairwise"](flat_topology(N))
tr = ShardMapTransport(N, "data")
buf = rng.normal(size=(N, sched.num_slots, 8, 3)).astype(np.float32)


def _runner(fn):
    # in_specs=P("data") hands each rank its [num_slots, 8, 3] slice
    f = jax.jit(compat.shard_map(
        fn, mesh=mesh1d, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))
    with jax.set_mesh(mesh1d):
        return np.asarray(f(buf.reshape((N * sched.num_slots, 8, 3))))


whole = _runner(lambda b: tr.run(sched, b))
for chunks in (1, 2, 4):
    got = _runner(lambda b, c=chunks: tr.run_chunked(
        sched, b, chunks=c))
    check(f"shardmap run_chunked chunks={chunks} bit-identical",
          np.array_equal(got, whole))

fold = _runner(lambda b: tr.run_chunked(
    sched, b, chunks=4,
    consume=lambda c, out, i: c + out.sum(axis=1),
    init=jnp.zeros((sched.num_slots, 3), jnp.float32)))
check("shardmap run_chunked early-bird fold == whole sum",
      np.allclose(fold, whole.reshape(N, sched.num_slots, 8, 3)
                  .sum(axis=2).reshape(N * sched.num_slots, 3),
                  atol=1e-4))

# ---------------------------------------------------------------------------
# 2. mpix_alltoall_overlap == mpix_alltoall
# ---------------------------------------------------------------------------
# per-rank input: N destination blocks of 6 rows each
xa = rng.normal(size=(N * N * 6, 5)).astype(np.float32)


def _a2a(algo):
    f = jax.jit(compat.shard_map(
        lambda v: mpix.mpix_alltoall(v, "data", algorithm=algo),
        mesh=mesh1d, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))
    with jax.set_mesh(mesh1d):
        return np.asarray(f(xa))


def _a2a_overlap(algo, chunks):
    rc = 6 // chunks

    def fold(carry, out_c, i):
        # out_c = the alltoall of row slice i of every block:
        # [N*rc, 5] -> rows [i*rc, (i+1)*rc) of each received block
        return jax.lax.dynamic_update_slice_in_dim(
            carry, out_c.reshape(N, rc, 5), i * rc, axis=1)

    f = jax.jit(compat.shard_map(
        lambda v: mpix.mpix_alltoall_overlap(
            v, "data", fold, jnp.zeros((N, 6, 5), jnp.float32),
            chunks=chunks, algorithm=algo).reshape(N * 6, 5),
        mesh=mesh1d, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))
    with jax.set_mesh(mesh1d):
        return np.asarray(f(xa))


for algo in ("xla", "pairwise", "bruck"):
    want = _a2a(algo)
    for chunks in (1, 2, 3, 6):
        got = _a2a_overlap(algo, chunks)
        check(f"alltoall_overlap algo={algo} chunks={chunks}",
              np.array_equal(got, want)
              or np.allclose(got, want, atol=1e-6))

# ---------------------------------------------------------------------------
# 3. MoE dispatch overlap == monolithic
# ---------------------------------------------------------------------------
mesh = compat.make_mesh((2, 4), ("data", "model"))
cfg = configs.get_smoke("moonshot-v1-16b-a3b")
mcfg = cfg.moe
p = moe_mod.init(jax.random.key(0), mcfg, cfg.d_model)
xm = (jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model),
                        jnp.float32) * 0.3)
outs = {}
for ov in (None, 2, 4, 0):
    disp = make_moe_dispatch(
        mesh, EPOptions(alltoall="pairwise",
                        capacity_factor=float(mcfg.n_experts),
                        overlap_chunks=ov),
        cfg.mlp_act)
    with jax.set_mesh(mesh):
        outs[ov] = np.asarray(jax.jit(
            lambda pp, xx: disp(pp, mcfg, xx))(p, xm), np.float32)
for ov in (2, 4, 0):
    check(f"moe dispatch overlap_chunks={ov} == monolithic",
          np.allclose(outs[ov], outs[None], atol=1e-5, rtol=1e-5))

# ---------------------------------------------------------------------------
# 4. explicit-DP step with grad-sync overlap == unpipelined step
# ---------------------------------------------------------------------------
cfg_t = configs.get_smoke("smollm-360m")
pipe = DataPipeline(PipelineConfig(vocab_size=cfg_t.vocab_size,
                                   seq_len=16, global_batch=4))
batch = pipe.batch(0)
base_opts = TrainOptions(dp_mode="explicit", remat=False, peak_lr=1e-3,
                         warmup_steps=1, total_steps=100)
over_opts = TrainOptions(dp_mode="explicit", remat=False, peak_lr=1e-3,
                         warmup_steps=1, total_steps=100,
                         overlap_grad_chunks=3)
state = init_train_state(jax.random.key(0), cfg_t, base_opts)
from jax.sharding import NamedSharding

results = {}
for tag, opts in (("base", base_opts), ("overlap", over_opts)):
    with jax.set_mesh(mesh):
        bsh = jax.device_put(batch, NamedSharding(mesh, P(("data",))))
        new, m = jax.jit(make_train_step(cfg_t, mesh, opts))(
            jax.device_put(state), bsh)
    results[tag] = (float(m["loss"]), float(m["grad_norm"]),
                    np.asarray(jax.tree.leaves(new["params"])[0],
                               np.float32))
l0, g0, w0 = results["base"]
l1, g1, w1 = results["overlap"]
check("overlap step same loss", abs(l0 - l1) < 1e-5)
check("overlap step same grad norm", abs(g0 - g1) < 1e-4 * max(1.0, g0))
check("overlap step same updated params", np.allclose(w0, w1, atol=1e-5))

# ---------------------------------------------------------------------------
# 5. serve prefill with explicit EP overlap == default XLA dispatch
# ---------------------------------------------------------------------------
from repro.models import model as M
from repro.serve.step import ServeOptions, make_prefill_step

params = M.init_params(jax.random.key(2), cfg)
toks = jax.random.randint(jax.random.key(3), (2, 16), 0, cfg.vocab_size)
sbatch = {"tokens": toks}
logits = {}
for tag, sopts in (
        ("default", ServeOptions()),
        ("ep_overlap", ServeOptions(ep_options=EPOptions(
            alltoall="pairwise",
            capacity_factor=float(mcfg.n_experts),
            overlap_chunks=2)))):
    with jax.set_mesh(mesh):
        logits[tag] = np.asarray(jax.jit(
            make_prefill_step(cfg, mesh, sopts))(params, sbatch),
            np.float32)
check("serve prefill EP overlap == default dispatch",
      np.allclose(logits["ep_overlap"], logits["default"],
                  atol=2e-2, rtol=2e-2))

if failures:
    raise SystemExit(f"FAILURES: {failures}")
print("ALL OK")
