"""Serve a small model with batched requests: batched prefill via the
forward pass + greedy KV-cache decode, measuring per-token latency.

    PYTHONPATH=src python examples/serve_batch.py
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.models import model as M
from repro.serve.step import ServeOptions, make_decode_step
from repro import compat

ARCH = "qwen3-14b"          # smoke-sized variant of the qwen3 family
BATCH, PROMPT, GEN = 8, 24, 24


def main():
    cfg = configs.get_smoke(ARCH)
    n = jax.device_count()
    mesh = compat.make_mesh((n, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        params = M.init_params(jax.random.key(0), cfg)
        reqs = jax.random.randint(jax.random.key(1), (BATCH, PROMPT), 2,
                                  cfg.vocab_size)
        cache = M.init_cache(cfg, BATCH, PROMPT + GEN)
        decode = jax.jit(make_decode_step(cfg, mesh, ServeOptions()))

        tok = reqs[:, :1]
        t0 = time.time()
        gen = []
        for i in range(PROMPT + GEN - 1):
            nxt, cache = decode(params, cache, tok)
            tok = reqs[:, i + 1: i + 2] if i + 1 < PROMPT else nxt
            if i + 1 >= PROMPT:
                gen.append(np.asarray(nxt)[:, 0])
        jax.block_until_ready(tok)
        dt = time.time() - t0
    gen = np.stack(gen, 1)
    steps = PROMPT + GEN - 1
    print(f"batch={BATCH} prompt={PROMPT} gen={GEN}: "
          f"{dt/steps*1e3:.1f} ms/step, "
          f"{BATCH*steps/dt:.0f} tok/s aggregate")
    assert gen.shape == (BATCH, GEN)
    print("serve_batch OK")


if __name__ == "__main__":
    main()
