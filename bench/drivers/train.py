"""Training driver: the program's jitted ``make_train_step`` over a ring
of distinct batches, the loss read back after every step as a logging
training loop does.

Set-up builds the step and its state once, drives it through the first
``check_steps`` steps on the ring's first batches (the first call
compiles), records what the check compares, and hands the same step and
state to the window.  After the window the state is freed and the
reference repeats those first steps.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import compare, counts, program, traffic
from bench.harness import Check, Window
from bench.reference import smollm as ref


class Driver:
    def __init__(self, ctx):
        self.ctx, self.c, self.w = ctx, ctx.config, ctx.workload
        self.o = dict(self.w["optimizer"])

    def setup(self) -> None:
        from repro.core import api
        from repro.optim import adamw_init
        from repro.train.step import TrainOptions, make_train_step

        w, c, o = self.w, self.c, self.o
        dp = w["data_parallel"]
        if dp != len(self.ctx.devices):
            raise ValueError(f"data_parallel {dp} on "
                             f"{len(self.ctx.devices)} chips")
        auto = jax.sharding.AxisType.Auto
        self.mesh = jax.make_mesh((dp, 1), ("data", "model"),
                                  devices=self.ctx.devices,
                                  axis_types=(auto, auto))
        api.set_default_policy(w["select_policy"])
        opts = TrainOptions(
            dp_mode=w["dp_mode"], dp_algorithm=w["dp_algorithm"],
            dp_transport=w["dp_transport"], remat=w["remat"],
            peak_lr=o["peak_lr"], warmup_steps=o["warmup_steps"],
            total_steps=o["total_steps"], max_grad_norm=o["max_grad_norm"],
            weight_decay=o["weight_decay"])
        rep = NamedSharding(self.mesh, P())
        rows = NamedSharding(self.mesh, P("data"))
        self.kp, kd = jax.random.split(ref.seed_key(self.ctx.seed))

        def init_state(k):
            params = program.program_params(ref.init_params(k, c))
            return {"params": params, "opt": adamw_init(params),
                    "step": jnp.zeros((), jnp.int32)}

        log, t0 = self.ctx.log, time.perf_counter()
        with jax.set_mesh(self.mesh):
            state = jax.jit(init_state, out_shardings=rep)(self.kp)
            self.ring = jax.jit(
                lambda k: traffic.batches(
                    k, w["ring"], w["global_batch"], w["seq_len"],
                    c["vocab_size"], w["mean_doc_len"], w["bos_id"]),
                out_shardings=rows)(kd)
            self.step = jax.jit(make_train_step(
                program.model_config(c), self.mesh, opts))
            grad_norms = jax.jit(lambda mu: ref.leaf_norms(
                program.reference_params(mu)))
            change_norms = jax.jit(lambda p, k: ref.leaf_norms(
                jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             - b.astype(jnp.float32),
                             program.reference_params(p),
                             ref.init_params(k, c))))
            jax.block_until_ready((state, self.ring))
            log(f"setup: state and batches made {time.perf_counter() - t0:.3f}")
            self.losses = []
            for i in range(w["check_steps"]):
                state, m = self.step(state, self.ring[i])
                self.losses.append(float(m["loss"]))
                log(f"setup: step {i + 1} done {time.perf_counter() - t0:.3f}")
                if i == 0:
                    # Adam's first moment after one step is (1 - b1) g
                    mu = jax.device_get(grad_norms(state["opt"]["mu"]))
                    self.grad = {k: float(v) / (1.0 - o["b1"])
                                 for k, v in mu.items()}
            self.change = {k: float(v) for k, v in jax.device_get(
                change_norms(state["params"], self.kp)).items()}
        self.state = state

    def window(self, seconds: float, traced: bool) -> Window:
        w = self.w
        ring, n = self.ring, len(self.ring)
        i = w["check_steps"]
        steps = bad = 0
        state = self.state
        with jax.set_mesh(self.mesh), jax.profiler.TraceAnnotation("window"):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.StepTraceAnnotation("train", step_num=i):
                    with jax.profiler.TraceAnnotation("step_dispatch"):
                        state, m = self.step(state, ring[i % n])
                    with jax.profiler.TraceAnnotation("loss_readback"):
                        loss = float(m["loss"])
                steps += 1
                i += 1
                bad += not np.isfinite(loss)
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds:
                    break
        self.state = state
        tokens = steps * w["global_batch"] * w["seq_len"]
        return Window(elapsed, steps, bad,
                      {"train_tokens_per_s": tokens / elapsed},
                      {"steps": steps, "tokens": tokens})

    def counts(self) -> dict:
        return {"flops_per_token": counts.train_flops_per_token(
            self.c, self.w["seq_len"])}

    def check(self) -> list:
        """Free the program's state, then run the reference's first
        steps on the same batches and compare."""
        lim = self.ctx.limits["limits"]
        got = numbers((self.losses, self.grad, self.change),
                      self.reference())
        for k in self.ctx.limits.get("not_compared", []):
            self.ctx.log(f"reading {k} {got[k]!r} not compared")
        return [Check(k, v, lim[k]) for k, v in got.items() if k in lim]

    def reference(self, **fault):
        """``ref.train_run`` over the check's batches on the first chip;
        frees the program's state first."""
        n = self.w["check_steps"]
        dev = self.ctx.devices[0]
        if hasattr(self, "state"):
            self.first = jax.device_put(self.ring[:n], dev)
            del self.state, self.ring, self.step
        return ref.train_run(jax.device_put(self.kp, dev), self.first,
                             self.c, self.o, **fault)


def numbers(run, reference) -> dict:
    """The compared numbers of a run's (losses, first gradient norms,
    change norms) against the reference's."""
    losses, grad, change = run
    r_losses, r_grad, r_change = reference
    return {"loss_gap": compare.loss_gap(losses, r_losses),
            "grad_norm_gap": compare.norm_gap(grad, r_grad),
            "update_norm_gap": compare.norm_gap(
                change, r_change, keep=compare.moved_leaves(r_grad))}
