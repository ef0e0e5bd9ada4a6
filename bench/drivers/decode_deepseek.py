"""Decode driver for a ``deepseek_v3`` configuration (Moonlight-16B-A3B
on the chip that holds one expert share): the ``decode`` driver's
protocol and window, with this configuration's program adapter,
reference and counts.

Set-up makes the weights and ``batch`` prompts from the seed and
prefills them into the latent cache token by token through the
program's ``jit_decode_step``.  The window runs rounds of ``new_tokens``
greedy steps from the prefilled cache and reads every step's tokens
back (``bench.drivers.decode``).  The check frees the program's state
and runs sampled requests through ``bench.reference.deepseek``: the
widest and the mean gap of the served tokens' logits below the
reference's best.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, counts_deepseek, program_deepseek as program, \
    traffic
from bench.drivers import decode
from bench.harness import Check
from bench.reference import deepseek as ref


class Driver(decode.Driver):
    def setup(self) -> None:
        from repro.serve.step import (ServeOptions, init_serve_cache,
                                      jit_decode_step, place, token_spec)
        w, c = self.w, self.c
        if w["prompt_len"] + w["new_tokens"] - 1 > w["cache_len"]:
            raise ValueError("cache_len cannot hold prompt and answer")
        mcfg = program.model_config(c)
        auto = jax.sharding.AxisType.Auto
        mesh = jax.make_mesh((len(self.ctx.devices), 1), ("data", "model"),
                             devices=self.ctx.devices,
                             axis_types=(auto, auto))
        self.mesh = mesh
        opts = ServeOptions()
        self.kp, kd = jax.random.split(ref.seed_key(self.ctx.seed))
        with jax.set_mesh(mesh):
            params = jax.jit(lambda k: program.program_params(
                ref.init_params(k, c), c))(self.kp)
            cache = init_serve_cache(mcfg, w["batch"], w["cache_len"])
            self.decode, (pspec, cspec) = jit_decode_step(
                mcfg, mesh, opts, params, cache)
            self.params = place(mesh, params, pspec)
            cache = place(mesh, cache, cspec)
            self.prompts = np.asarray(jax.jit(lambda k: traffic.batch(
                k, w["batch"], w["prompt_len"], c["vocab_size"],
                w["mean_doc_len"], w["bos_id"])["tokens"])(kd))
            self.ctx.log("setup: weights and prompts made")
            tspec = token_spec(mesh, opts)
            self._tok = lambda t: place(mesh, t, tspec)
            for i in range(w["prompt_len"] - 1):
                # one step in flight: a step's input and output caches
                # are a third of the chip's memory
                _, cache = self.decode(self.params, cache,
                                       self._tok(self.prompts[:, i:i + 1]))
                jax.block_until_ready(cache)
            self.cache = cache
            self.last = self._tok(self.prompts[:, -1:])
            jax.block_until_ready(self.cache)
        self.served = []            # (round, tokens [batch, new_tokens])

    def counts(self) -> dict:
        w, c = self.w, self.c
        ctx = counts.mean_context(w["prompt_len"], w["new_tokens"])
        return {"flops_per_token":
                counts_deepseek.decode_flops_per_token(c, ctx),
                "bytes_per_step": counts_deepseek.decode_bytes_per_step(
                    c, w["batch"], ctx)}

    def check(self) -> list:
        """Free the program's state; run the reference once over each
        sampled request's prompt and served tokens.  ``served_logit_gap``
        is the widest gap by which a served token's logit lies below the
        reference's best (as in ``bench.drivers.decode``);
        ``served_logit_gap_mean`` is the mean gap over every served
        position.  A routing choice that rounding flips near a tie moves
        a token's hidden state by a whole expert's share, so the widest
        gap has a long tail; the mean is what a lower precision raises
        everywhere."""
        g = self.position_gaps(self.sample())
        lim = self.ctx.limits["limits"]
        return [Check("served_logit_gap", float(g.max()),
                      lim["served_logit_gap"]),
                Check("served_logit_gap_mean", float(g.mean()),
                      lim["served_logit_gap_mean"])]

    def gaps(self, seqs, fp8_pick=False) -> float:
        return float(self.position_gaps(seqs, fp8_pick).max())

    def position_gaps(self, seqs, fp8_pick=False) -> np.ndarray:
        """[requests, new_tokens]: each served position's gap."""
        dev = self.ctx.devices[0]
        return np.asarray(jax.device_get(reference_gaps(
            jax.device_put(self.kp, dev), jax.device_put(seqs, dev),
            self.c, self.w["prompt_len"], fp8_pick)))


def reference_gaps(key, seqs, c, prompt_len, fp8_pick=False):
    """Per sequence and served position, the gap by which the
    reference's logit of the served token lies below its best logit
    there (one request at a time; the head only over the served
    positions).

    ``fp8_pick``: the control.  The token at each position is the one
    the fp8 reference puts first, judged by the float32 reference."""
    def run(k, s):
        params = ref.init_params(k, c)

        def one(seq):
            lg = ref.logits(params, c, seq[:-1], start=prompt_len - 1)
            if fp8_pick:
                tok = jnp.argmax(ref.logits(params, c, seq[:-1],
                                            start=prompt_len - 1, fp8=True),
                                 -1)
            else:
                tok = seq[prompt_len:]
            got = jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]
            return jnp.max(lg, -1) - got

        return jax.lax.map(one, s)

    return jax.jit(run)(key, seqs)
