"""Subprocess check: elastic checkpoint restore — train on a (4,2)
mesh, checkpoint, restart on a (2,4) mesh (different shard decomposition
and per-device batch), and verify the training trajectory is unchanged
vs an uninterrupted run."""
import os
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.data import DataPipeline, PipelineConfig
from repro.train.sharding import data_axes, param_specs
from repro import compat
from repro.train.step import TrainOptions, init_train_state, \
    make_train_step

cfg = configs.get_smoke("smollm-360m")
opts = TrainOptions(dp_mode="fsdp", remat=False, peak_lr=1e-3,
                    warmup_steps=1, total_steps=100)
pipe = PipelineConfig(vocab_size=cfg.vocab_size, seq_len=16,
                      global_batch=8, seed=11)


def run(mesh, state, steps, start):
    dp = DataPipeline(pipe)
    step_fn = jax.jit(make_train_step(cfg, mesh, opts))
    with jax.set_mesh(mesh):
        state = jax.device_put(state)
        for s in range(start, start + steps):
            b = jax.device_put(
                dp.batch(s),
                NamedSharding(mesh, P(data_axes(mesh))))
            state, m = step_fn(state, b)
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state), \
        float(m["loss"])


mesh_a = compat.make_mesh((4, 2), ("data", "model"))
mesh_b = compat.make_mesh((2, 4), ("data", "model"))

state0 = init_train_state(jax.random.key(0), cfg, opts)

# uninterrupted 6 steps on mesh A
full, loss_full = run(mesh_a, state0, 6, 0)

# 3 steps on mesh A -> checkpoint -> restore -> 3 steps on mesh B
half, _ = run(mesh_a, state0, 3, 0)
with tempfile.TemporaryDirectory() as d:
    save_checkpoint(d, 3, half, num_shards=2)
    restored, _ = restore_checkpoint(d, half)
resumed, loss_res = run(mesh_b, restored, 3, 3)

w_full = np.concatenate([x.ravel() for x in jax.tree.leaves(
    full["params"])]).astype(np.float32)
w_res = np.concatenate([x.ravel() for x in jax.tree.leaves(
    resumed["params"])]).astype(np.float32)
err = np.abs(w_full - w_res).max()
print(f"trajectory match after elastic remesh: max|dw| = {err:.2e}, "
      f"loss {loss_full:.4f} vs {loss_res:.4f}")
assert err < 2e-2, err
# loss reduction order differs across mesh decompositions (bf16 matmuls
# reduced over different shard shapes), so the loss needs slightly more
# headroom than the weights
assert abs(loss_full - loss_res) < 3e-2
print("ALL OK")
