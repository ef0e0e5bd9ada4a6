"""Residual block assembly: (norm -> mixer -> [norm] -> residual) +
(norm -> ff -> [norm] -> residual), with optional cross-attention sublayer.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import attention, mamba, mla, mlp, moe, rwkv
from repro.models.common import dense_init, rmsnorm, split_keys
from repro.models.config import BlockSpec, ModelConfig


def _norm_scale(cfg: ModelConfig, d):
    # gemma parameterizes rmsnorm as (1 + w) with w ~ 0; others as w ~ 1
    return (jnp.zeros if cfg.gemma_norm else jnp.ones)((d,), jnp.bfloat16)


def init_block(key, spec: BlockSpec, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    ks = split_keys(key, ["mixer", "cross", "ff"])
    p = {"norm_mixer": _norm_scale(cfg, d)}
    if spec.mixer == "attn":
        p["attn"] = attention.init(ks["mixer"], cfg.attn, d)
    elif spec.mixer == "mla":
        p["mla"] = mla.init(ks["mixer"], cfg.mla, d)
    elif spec.mixer == "rwkv":
        p["rwkv"] = rwkv.init(ks["mixer"], cfg.rwkv, d)
    elif spec.mixer == "mamba":
        p["mamba"] = mamba.init(ks["mixer"], cfg.mamba, d)
    if spec.cross:
        p["norm_cross"] = _norm_scale(cfg, d)
        cross_cfg = dataclasses.replace(cfg.attn, cross=True, causal=False)
        p["cross"] = attention.init(ks["cross"], cross_cfg, d)
    if cfg.post_block_norm:
        p["norm_mixer_post"] = _norm_scale(cfg, d)
    if spec.ff != "none":
        p["norm_ff"] = _norm_scale(cfg, d)
        if cfg.post_block_norm:
            p["norm_ff_post"] = _norm_scale(cfg, d)
    if spec.ff == "mlp":
        p["mlp"] = mlp.init(ks["ff"], d, cfg.d_ff, cfg.gated_mlp)
    elif spec.ff == "moe":
        p["moe"] = moe.init(ks["ff"], cfg.moe, d)
    elif spec.ff == "cmix":
        p["cmix"] = rwkv.channel_mix_init(ks["ff"], d, cfg.d_ff)
    return p


def _norm(cfg, x, w):
    return rmsnorm(x, w, cfg.norm_eps, gemma_style=cfg.gemma_norm)


def forward(p, spec: BlockSpec, cfg: ModelConfig, x, *, positions,
            cross_src=None, use_kernel=False, moe_dispatch=None):
    """Full-sequence block; x [B, S, d]."""
    from repro.models.common import shard_hint
    if spec.mixer in ("attn", "mla"):
        # level-2 hint: sequence-parallel residual stream (no-op unless
        # the launcher enabled it)
        x = shard_hint(x, "residual")
    h = _norm(cfg, x, p["norm_mixer"])
    if spec.mixer == "attn":
        h = attention.forward(p["attn"], cfg.attn, h, positions=positions,
                              window=spec.window, eps=cfg.norm_eps,
                              use_kernel=use_kernel)
    elif spec.mixer == "mla":
        h = mla.forward(p["mla"], cfg.mla, h, positions=positions,
                        eps=cfg.norm_eps, use_kernel=use_kernel)
    elif spec.mixer == "rwkv":
        h = rwkv.time_mix(p["rwkv"], cfg.rwkv, h, use_kernel=use_kernel)
    elif spec.mixer == "mamba":
        h = mamba.forward(p["mamba"], cfg.mamba, h, eps=cfg.norm_eps,
                          use_kernel=use_kernel)
    else:
        h = jnp.zeros_like(h)
    if cfg.post_block_norm:
        h = _norm(cfg, h, p["norm_mixer_post"])
    x = x + h
    if spec.cross:
        h = _norm(cfg, x, p["norm_cross"])
        h = attention.forward(
            p["cross"], dataclasses.replace(cfg.attn, cross=True,
                                            causal=False),
            h, positions=positions, kv_src=cross_src, eps=cfg.norm_eps)
        x = x + h
    if spec.ff == "none":
        return x
    h = _norm(cfg, x, p["norm_ff"])
    if spec.ff == "mlp":
        h = mlp.forward(p["mlp"], h, cfg.mlp_act)
    elif spec.ff == "moe":
        if moe_dispatch is None:
            h = moe.forward(p["moe"], cfg.moe, h, cfg.mlp_act)
        else:
            h = moe_dispatch(p["moe"], cfg.moe, h)
    elif spec.ff == "cmix":
        h = rwkv.channel_mix(p["cmix"], h)
    if cfg.post_block_norm:
        h = _norm(cfg, h, p["norm_ff_post"])
    return x + h


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def init_cache(spec: BlockSpec, cfg: ModelConfig, batch: int, max_len: int):
    c = {}
    if spec.mixer == "attn":
        c["attn"] = attention.init_cache(cfg.attn, batch, max_len)
    elif spec.mixer == "mla":
        c["mla"] = mla.init_cache(cfg.mla, batch, max_len)
    elif spec.mixer == "rwkv":
        c["rwkv"] = rwkv.init_state(cfg.rwkv, batch, cfg.d_model)
    elif spec.mixer == "mamba":
        c["mamba"] = mamba.init_state(cfg.mamba, batch, cfg.d_model)
    if spec.ff == "cmix":
        c["cmix"] = {"x_cm": jnp.zeros((batch, cfg.d_model), jnp.bfloat16)}
    return c


def decode(p, spec: BlockSpec, cfg: ModelConfig, x, cache, *,
           cross_src=None):
    """One-token decode; x [B, 1, d].  An MoE feed-forward runs under
    the named scope ``decode.moe`` (router, held and shared experts)."""
    h = _norm(cfg, x, p["norm_mixer"])
    if spec.mixer == "attn":
        h, cache["attn"] = attention.decode_step(
            p["attn"], cfg.attn, h, cache["attn"], window=spec.window,
            eps=cfg.norm_eps)
    elif spec.mixer == "mla":
        h, cache["mla"] = mla.decode_step(p["mla"], cfg.mla, h,
                                          cache["mla"], eps=cfg.norm_eps)
    elif spec.mixer == "rwkv":
        h, cache["rwkv"] = rwkv.decode_time_mix(p["rwkv"], cfg.rwkv, h,
                                                cache["rwkv"])
    elif spec.mixer == "mamba":
        h, cache["mamba"] = mamba.decode_step(p["mamba"], cfg.mamba, h,
                                              cache["mamba"],
                                              eps=cfg.norm_eps)
    else:
        h = jnp.zeros_like(h)
    if cfg.post_block_norm:
        h = _norm(cfg, h, p["norm_mixer_post"])
    x = x + h
    if spec.cross:
        h = _norm(cfg, x, p["norm_cross"])
        h = attention.forward(
            p["cross"], dataclasses.replace(cfg.attn, cross=True,
                                            causal=False),
            h, positions=jnp.zeros((x.shape[0], 1), jnp.int32),
            kv_src=cross_src, eps=cfg.norm_eps)
        x = x + h
    if spec.ff == "none":
        return x, cache
    h = _norm(cfg, x, p["norm_ff"])
    if spec.ff == "mlp":
        h = mlp.forward(p["mlp"], h, cfg.mlp_act)
    elif spec.ff == "moe":
        # exact over the held experts: each sees at most B tokens, so
        # every routed slot is computed, none dropped
        with jax.named_scope("decode.moe"):
            h = moe.forward(p["moe"], cfg.moe, h, cfg.mlp_act)
    elif spec.ff == "cmix":
        h, cache["cmix"] = rwkv.decode_channel_mix(p["cmix"], h,
                                                   cache["cmix"])
    if cfg.post_block_norm:
        h = _norm(cfg, h, p["norm_ff_post"])
    return x + h, cache
