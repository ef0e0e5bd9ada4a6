"""Serving steps: batched prefill + KV-cache decode.

Sharding (sharding.cache_specs):
  * decode_32k  — batch over (pod, data), heads over model.
  * long_500k   — batch 1: KV / recurrent state sequence-sharded over
    the data axes (sequence parallelism); the partitioner turns the
    softmax over the sharded KV length into partial-softmax + psum (the
    log-sum-exp combine), so one decode step touches each chip's KV
    shard locally and crosses the wire with O(heads) scalars.
    Only the sub-quadratic archs (rwkv6, jamba) run this cell.

Decode greedily samples (argmax) to keep the step closed under jit;
the example driver shows temperature sampling on top of the logits.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import model as M
from repro.train import sharding
from repro.train.moe_dispatch import EPOptions, make_moe_dispatch


@dataclasses.dataclass(frozen=True)
class ServeOptions:
    use_kernel: bool = False
    long_context: bool = False       # SP cache layout (batch-1 decode)
    ep_options: EPOptions | None = None
    # explicit expert-parallel dispatch for MoE archs during prefill
    # (None = XLA-sharded default).  With overlap_chunks set, the
    # dispatch alltoall pipelines against the expert MLPs — the serve
    # hot path gets the same compute-comm overlap as training.
    resilience: object = None
    # chaos-resilient dispatch collectives: overrides ep_options'
    # resilience when both are set (the serve knob wins so launchers
    # can arm verification without rebuilding EPOptions).


def init_serve_cache(cfg, batch: int, max_len: int):
    return M.init_cache(cfg, batch, max_len)


def make_prefill_step(cfg, mesh, opts: ServeOptions) -> Callable:
    """(params, tokens[, frames/vision]) -> logits — full-sequence
    forward used for prompt processing; dry-run target of prefill_32k."""

    moe_dispatch = None
    if opts.ep_options is not None and cfg.moe is not None:
        ep_opts = opts.ep_options
        if opts.resilience is not None:
            ep_opts = dataclasses.replace(ep_opts,
                                          resilience=opts.resilience)
        moe_dispatch = make_moe_dispatch(mesh, ep_opts, cfg.mlp_act)

    def prefill(params, batch):
        kw = {}
        if cfg.encoder is not None:
            kw["encoder_frames"] = batch["encoder_frames"]
        if cfg.vision_prefix:
            kw["vision_embeds"] = batch["vision_embeds"]
        return M.forward(params, cfg, batch["tokens"],
                         use_kernel=opts.use_kernel,
                         moe_dispatch=moe_dispatch, **kw)

    return prefill


def make_decode_step(cfg, mesh, opts: ServeOptions) -> Callable:
    """(params, cache, tokens [B,1][, cross_src]) ->
    (next_tokens [B,1], cache').  ``cross_src`` is the precomputed
    encoder output for enc-dec archs (whisper)."""

    if cfg.encoder is not None:
        def decode_step(params, cache, tokens, cross_src):
            logits, cache = M.decode_step(params, cfg, cache, tokens,
                                          cross_src=cross_src)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return nxt[:, None], cache
        return decode_step

    def decode_step(params, cache, tokens):
        logits, cache = M.decode_step(params, cfg, cache, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt[:, None], cache

    return decode_step


def _shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def place(mesh, tree, spec_tree):
    """``device_put`` every leaf of ``tree`` to its spec on ``mesh`` —
    what ``jit_decode_step``'s ``in_shardings`` require of params and
    cache before the first call."""
    return jax.device_put(tree, _shardings(mesh, spec_tree))


def token_spec(mesh, opts: ServeOptions):
    """Spec of the [B, 1] token batch ``jit_decode_step`` takes."""
    return P() if opts.long_context else P(sharding.data_axes(mesh))


def jit_decode_step(cfg, mesh, opts: ServeOptions, params, cache):
    """jit the decode step with params/cache/token shardings; returns
    (step, (param_specs, cache_specs)).  Place params, cache and tokens
    with ``place`` (tokens on ``token_spec``) before each call."""
    pspec = sharding.param_specs(params, cfg, mesh)
    cspec = sharding.cache_specs(cache, cfg, mesh,
                                 long_context=opts.long_context)
    d_axes = sharding.data_axes(mesh)
    tok_spec = token_spec(mesh, opts)
    step = make_decode_step(cfg, mesh, opts)
    in_sh = [_shardings(mesh, pspec), _shardings(mesh, cspec),
             NamedSharding(mesh, tok_spec)]
    if cfg.encoder is not None:
        in_sh.append(NamedSharding(mesh, P(d_axes)))
    return jax.jit(step,
                   in_shardings=tuple(in_sh),
                   out_shardings=(NamedSharding(mesh, tok_spec),
                                  _shardings(mesh, cspec))), (pspec, cspec)
