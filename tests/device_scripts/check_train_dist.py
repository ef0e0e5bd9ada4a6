"""Subprocess check (8 host devices): the distributed train/serve paths.

  1. mpix EP dispatch == dense-dispatch oracle (generous capacity), for
     every alltoall algorithm, flat + pods meshes.
  2. explicit-DP (mpix allreduce, every algorithm) step == single-device
     step (same loss, same params after update).
  3. bucketed + compressed DCN sync run and stay finite.
  4. FSDP-sharded train step == single-device step (xla substrate).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.data import DataPipeline, PipelineConfig
from repro.models import moe as moe_mod
from repro.train.moe_dispatch import EPOptions, make_moe_dispatch
from repro.train.step import TrainOptions, init_train_state, make_train_step
from repro import compat

failures = []


def check(name, ok):
    print(f"{name:58s} {'ok' if ok else 'FAIL'}")
    if not ok:
        failures.append(name)


mesh_flat = compat.make_mesh((2, 4), ("data", "model"))
mesh_pods = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))

# ---------------------------------------------------------------------------
# 1. EP dispatch == dense oracle
# ---------------------------------------------------------------------------
cfg = configs.get_smoke("moonshot-v1-16b-a3b")   # 8 experts, sigmoid+bias
mcfg = cfg.moe
p = moe_mod.init(jax.random.key(0), mcfg, cfg.d_model)
x = (jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model), jnp.float32)
     * 0.3)
want = np.asarray(moe_mod.forward(p, mcfg, x, cfg.mlp_act), np.float32)

for mesh in (mesh_flat, mesh_pods):
    for algo in ("xla", "pairwise", "hierarchical"):
        disp = make_moe_dispatch(
            mesh, EPOptions(alltoall=algo,
                            capacity_factor=float(mcfg.n_experts)),
            cfg.mlp_act)
        with jax.set_mesh(mesh):
            got = np.asarray(jax.jit(lambda pp, xx: disp(pp, mcfg, xx))(
                p, x), np.float32)
        ok = np.allclose(got, want, atol=2e-2, rtol=2e-2)
        check(f"EP dispatch {mesh.axis_names} alltoall={algo}", ok)

# ---------------------------------------------------------------------------
# 2-4. train-step equivalence single-device vs distributed
# ---------------------------------------------------------------------------
cfg = configs.get_smoke("smollm-360m")
pipe = DataPipeline(PipelineConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                   global_batch=8, seed=3))
batch = pipe.batch(0)

mesh1 = compat.make_mesh((1, 1), ("data", "model"))
opts_ref = TrainOptions(dp_mode="fsdp", remat=False, peak_lr=1e-3,
                        warmup_steps=1, total_steps=100)
state0 = init_train_state(jax.random.key(0), cfg, opts_ref)
ref_state, ref_m = jax.jit(make_train_step(cfg, mesh1, opts_ref))(
    jax.device_put(state0), batch)
ref_loss = float(ref_m["loss"])
ref_w = np.asarray(jax.tree.leaves(ref_state["params"])[0], np.float32)

for mesh, algos in ((mesh_flat, ["xla", "ring_rs_ag", "hierarchical"]),
                    (mesh_pods, ["xla", "hierarchical"])):
    d_axes = tuple(a for a in mesh.axis_names if a != "model")
    for algo in algos:
        opts = TrainOptions(dp_mode="explicit", dp_algorithm=algo,
                            remat=False, peak_lr=1e-3, warmup_steps=1,
                            total_steps=100)
        step = make_train_step(cfg, mesh, opts)
        with jax.set_mesh(mesh):
            bsh = jax.device_put(batch, NamedSharding(mesh, P(d_axes)))
            st = jax.device_put(state0)
            new, m = jax.jit(step)(st, bsh)
        w = np.asarray(jax.tree.leaves(new["params"])[0], np.float32)
        ok = (abs(float(m["loss"]) - ref_loss) < 1e-2
              and np.allclose(w, ref_w, atol=1e-2))
        check(f"explicit DP {mesh.axis_names} algo={algo} == 1-dev", ok)

# bucketed sync
opts = TrainOptions(dp_mode="explicit", dp_algorithm="ring_rs_ag",
                    grad_buckets=4, remat=False, peak_lr=1e-3,
                    warmup_steps=1, total_steps=100)
with jax.set_mesh(mesh_flat):
    bsh = jax.device_put(batch, NamedSharding(mesh_flat, P(("data",))))
    new, m = jax.jit(make_train_step(cfg, mesh_flat, opts))(
        jax.device_put(state0), bsh)
w = np.asarray(jax.tree.leaves(new["params"])[0], np.float32)
check("bucketed explicit DP == 1-dev",
      abs(float(m["loss"]) - ref_loss) < 1e-2
      and np.allclose(w, ref_w, atol=1e-2))

# compressed DCN sync (int8 quantization -> looser equivalence)
opts = TrainOptions(dp_mode="explicit", compress_dcn=True, remat=False,
                    peak_lr=1e-3, warmup_steps=1, total_steps=100)
state_c = init_train_state(jax.random.key(0), cfg, opts)
with jax.set_mesh(mesh_pods):
    bsh = jax.device_put(batch,
                         NamedSharding(mesh_pods, P(("pod", "data"))))
    new, m = jax.jit(make_train_step(cfg, mesh_pods, opts))(
        jax.device_put(state_c), bsh)
w = np.asarray(jax.tree.leaves(new["params"])[0], np.float32)
check("compressed DCN sync finite + close",
      np.isfinite(float(m["loss"])) and np.allclose(w, ref_w, atol=5e-2))

# FSDP path on 8 devices
from repro.train.step import jit_train_step
opts = TrainOptions(dp_mode="fsdp", remat=True, peak_lr=1e-3,
                    warmup_steps=1, total_steps=100)
with jax.set_mesh(mesh_flat):
    bspec = jax.tree.map(lambda _: P(("data",)), batch)
    step, sspec = jit_train_step(cfg, mesh_flat, opts,
                                 state0, bspec)
    new, m = step(jax.device_put(state0), batch)
w = np.asarray(jax.tree.leaves(new["params"])[0], np.float32)
check("FSDP 8-dev step == 1-dev", abs(float(m["loss"]) - ref_loss) < 1e-2
      and np.allclose(w, ref_w, atol=1e-2))

if failures:
    raise SystemExit(f"FAILURES: {failures}")
print("ALL OK")
