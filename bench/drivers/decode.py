"""Decode driver: batched greedy decode through the program's
``jit_decode_step``, the way ``repro.launch.serve`` drives it.

Set-up makes the weights and ``batch`` prompts from the seed and
prefills them into the cache token by token through the same step, as
the serve launcher does.  The window then runs rounds: each round starts
``batch`` requests from the prefilled cache and the last prompt token,
decodes ``new_tokens`` tokens each, and reads every step's tokens back
to the host, as a server that streams them does.  After the window the
program's state is freed and a sample of the finished requests, drawn
from the seed, is run through the plain reference.
"""
from __future__ import annotations

import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, program, traffic
from bench.harness import Check, Window
from bench.reference import smollm as ref


class Driver:
    def __init__(self, ctx):
        self.ctx, self.c, self.w = ctx, ctx.config, ctx.workload

    def setup(self) -> None:
        from repro.serve.step import (ServeOptions, init_serve_cache,
                                      jit_decode_step, place, token_spec)
        w, c = self.w, self.c
        if w["prompt_len"] + w["new_tokens"] - 1 > w["cache_len"]:
            raise ValueError("cache_len cannot hold prompt and answer")
        auto = jax.sharding.AxisType.Auto
        mesh = jax.make_mesh((len(self.ctx.devices), 1), ("data", "model"),
                             devices=self.ctx.devices,
                             axis_types=(auto, auto))
        self.mesh = mesh
        opts = ServeOptions()
        mcfg = program.model_config(c)
        self.kp, kd = jax.random.split(ref.seed_key(self.ctx.seed))
        with jax.set_mesh(mesh):
            params = jax.jit(lambda k: program.program_params(
                ref.init_params(k, c)))(self.kp)
            cache = init_serve_cache(mcfg, w["batch"], w["cache_len"])
            self.decode, (pspec, cspec) = jit_decode_step(
                mcfg, mesh, opts, params, cache)
            self.params = place(mesh, params, pspec)
            cache = place(mesh, cache, cspec)
            self.prompts = np.asarray(jax.jit(lambda k: traffic.batch(
                k, w["batch"], w["prompt_len"], c["vocab_size"],
                w["mean_doc_len"], w["bos_id"])["tokens"])(kd))
            self.ctx.log(f"setup: weights and prompts made")
            tspec = token_spec(mesh, opts)
            self._tok = lambda t: place(mesh, t, tspec)
            for i in range(w["prompt_len"] - 1):
                _, cache = self.decode(self.params, cache,
                                       self._tok(self.prompts[:, i:i + 1]))
                if i % 2:   # at most two steps, each with its caches, queued
                    jax.block_until_ready(cache)
            self.cache = cache
            self.last = self._tok(self.prompts[:, -1:])
            jax.block_until_ready(self.cache)
        self.served = []            # (round, tokens [batch, new_tokens])

    def window(self, seconds: float, traced: bool) -> Window:
        w = self.w
        keep = random.Random(self.ctx.seed)
        rounds = steps = bad = 0
        with jax.set_mesh(self.mesh), jax.profiler.TraceAnnotation("window"):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("cache_restore"):
                    cache, tok = self.cache, self.last
                out = np.empty((w["batch"], w["new_tokens"]), np.int32)
                for j in range(w["new_tokens"]):
                    with jax.profiler.StepTraceAnnotation("decode",
                                                          step_num=steps):
                        with jax.profiler.TraceAnnotation("decode_dispatch"):
                            tok, cache = self.decode(self.params, cache, tok)
                        with jax.profiler.TraceAnnotation("token_readback"):
                            out[:, j] = np.asarray(tok)[:, 0]
                    steps += 1
                del cache
                bad += int((~((out >= 0) & (out < self.c["vocab_size"]))
                            .all(axis=1)).sum())
                # reservoir sample of one round to check, drawn from the seed
                if keep.randrange(rounds + 1) == 0:
                    self.served = [(rounds, out)]
                rounds += 1
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds:
                    break
        tokens = steps * w["batch"]
        return Window(elapsed, rounds * w["batch"], bad,
                      {"decode_tokens_per_s": tokens / elapsed},
                      {"steps": steps, "tokens": tokens, "rounds": rounds})

    def counts(self) -> dict:
        w, c = self.w, self.c
        ctx = counts.mean_context(w["prompt_len"], w["new_tokens"])
        return {"flops_per_token": counts.decode_flops_per_token(c, ctx),
                "bytes_per_step": counts.decode_bytes_per_step(
                    c, w["batch"], ctx)}

    def check(self) -> list:
        """Free the program's state; run the reference once over each
        sampled request's prompt and served tokens, and compare the
        widest gap by which a served token's logit lies below the
        reference's best."""
        gap = self.gaps(self.sample())
        return [Check("served_logit_gap", gap,
                      self.ctx.limits["limits"]["served_logit_gap"])]

    def sample(self):
        """[check_requests, prompt_len + new_tokens]: prompts and served
        tokens of requests drawn from the seed; frees the program's
        state."""
        w = self.w
        if hasattr(self, "cache"):
            del self.cache, self.params, self.last, self.decode
        _, out = self.served[0]
        pick = random.Random(self.ctx.seed + 1).sample(
            range(w["batch"]), w["check_requests"])
        return np.concatenate([self.prompts[pick], out[pick]], axis=1)

    def gaps(self, seqs, fp8_pick=False) -> float:
        dev = self.ctx.devices[0]
        return float(np.max(jax.device_get(reference_gaps(
            jax.device_put(self.kp, dev), jax.device_put(seqs, dev),
            self.c, self.w["prompt_len"], fp8_pick))))


def reference_gaps(key, seqs, c, prompt_len, fp8_pick=False):
    """Per sequence, the widest gap by which the reference's logit of a
    served token lies below its best logit at that position.

    ``fp8_pick``: the control.  The token at each position is the one
    the fp8 reference puts first, judged by the float32 reference."""
    def run(k, s):
        params = ref.init_params(k, c)

        def one(seq):
            lg = ref.logits(params, c, seq[:-1])[prompt_len - 1:]
            if fp8_pick:
                tok = jnp.argmax(ref.logits(params, c, seq[:-1], fp8=True)
                                 [prompt_len - 1:], -1)
            else:
                tok = seq[prompt_len:]
            got = jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]
            return jnp.max(jnp.max(lg, -1) - got)

        return jax.lax.map(one, s)

    return jax.jit(run)(key, seqs)
