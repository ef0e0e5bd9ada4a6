"""Multi-head latent attention (DeepSeek-V2/V3).

Queries and KV are projected through low-rank bottlenecks (the query
directly where ``q_lora_rank`` is None); the rope part of the key is
shared across heads (computed from the input, not the latent).  Cache
stores only the compressed latent + rope key: decode memory per token is
kv_lora_rank + qk_rope_head_dim — the MLA win.

Training and prefill up-project the latent to per-head keys and values
(``_attend``, the published training form).  Decode attends in latent
space (``decode_step``): ``W_UK`` is absorbed into the query and
``W_UV`` into the output (DeepSeek-V2, section 2.1.2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import apply_rope, dense_init, rmsnorm, split_keys
from repro.models.config import MLAConfig


def init(key, cfg: MLAConfig, d_model: int) -> dict:
    ks = split_keys(key, ["dq", "uq", "dkv", "uk", "uv", "kr", "o"])
    H = cfg.n_heads
    if cfg.q_lora_rank is None:
        q = {"w_q": dense_init(ks["uq"], (d_model, H * cfg.qk_head_dim))}
    else:
        q = {"w_dq": dense_init(ks["dq"], (d_model, cfg.q_lora_rank)),
             "q_norm": jnp.ones((cfg.q_lora_rank,), jnp.bfloat16),
             "w_uq": dense_init(ks["uq"],
                                (cfg.q_lora_rank, H * cfg.qk_head_dim))}
    return q | {
        "w_dkv": dense_init(ks["dkv"], (d_model, cfg.kv_lora_rank)),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.bfloat16),
        "w_uk": dense_init(ks["uk"],
                           (cfg.kv_lora_rank, H * cfg.qk_nope_head_dim)),
        "w_uv": dense_init(ks["uv"], (cfg.kv_lora_rank, H * cfg.v_head_dim)),
        "w_kr": dense_init(ks["kr"], (d_model, cfg.qk_rope_head_dim)),
        "wo": dense_init(ks["o"], (H * cfg.v_head_dim, d_model)),
    }


def _latents(p, cfg: MLAConfig, x, positions, eps):
    B, S, _ = x.shape
    H = cfg.n_heads
    if cfg.q_lora_rank is None:
        q = x @ p["w_q"]
    else:
        q = rmsnorm(x @ p["w_dq"], p["q_norm"], eps) @ p["w_uq"]
    q = q.reshape(B, S, H, cfg.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv = rmsnorm(x @ p["w_dkv"], p["kv_norm"], eps)        # [B,S,r]
    k_rope = apply_rope((x @ p["w_kr"])[:, :, None, :], positions,
                        cfg.rope_theta)                     # [B,S,1,dr]
    if S > 1:
        from repro.models.common import shard_hint
        ckv = shard_hint(ckv, "kv_full")    # SP: latents span the seq
        k_rope = shard_hint(k_rope, "kv_full")
    return q_nope, q_rope, ckv, k_rope


def _attend(p, cfg: MLAConfig, q_nope, q_rope, ckv, k_rope, mask,
            kv=None):
    B, Sq, H, _ = q_nope.shape
    Sk = ckv.shape[1]
    if kv is None:
        k_nope = (ckv @ p["w_uk"]).reshape(B, Sk, H, cfg.qk_nope_head_dim)
        v = (ckv @ p["w_uv"]).reshape(B, Sk, H, cfg.v_head_dim)
    else:
        k_nope, v = kv
    scale = cfg.qk_head_dim ** -0.5
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q_nope.astype(jnp.float32),
                         k_nope.astype(jnp.float32))
              + jnp.einsum("bqhd,bkod->bhqk", q_rope.astype(jnp.float32),
                           k_rope.astype(jnp.float32))) * scale
    from repro.models.common import shard_hint
    logits = shard_hint(logits, "attn_logits")
    logits = jnp.where(mask[:, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)
    return out.reshape(B, Sq, -1) @ p["wo"]


CHUNK_THRESHOLD = 8192
CHUNK_Q = 512


def forward(p, cfg: MLAConfig, x, *, positions, eps=1e-6,
            use_kernel=False, **_):
    B, S, _ = x.shape
    q_nope, q_rope, ckv, k_rope = _latents(p, cfg, x, positions, eps)
    if use_kernel:
        import os
        if os.environ.get("REPRO_KERNEL_SURROGATE") == "1" \
                and jax.default_backend() == "cpu":
            # flash-MLA HBM signature (dry-run only): q + latent streams
            # in, context out; no [Sq, Sk] scores in HBM.
            H = cfg.n_heads
            mix = (ckv.astype(jnp.float32) @ p["w_uv"]) \
                .reshape(B, S, H, cfg.v_head_dim)
            out = (q_nope.astype(jnp.float32).sum(-1, keepdims=True)
                   + q_rope.astype(jnp.float32).sum(-1, keepdims=True)
                   + k_rope.astype(jnp.float32).sum((-1, -2))[..., None,
                                                              None]
                   + mix)
            return out.reshape(B, S, -1).astype(x.dtype) @ p["wo"]
        # real TPU path: flash kernel on up-projected heads (a fused
        # latent-space MLA kernel is future work, see DESIGN.md)
        from repro.kernels.attention import ops as attn_ops
        k_nope = (ckv @ p["w_uk"]).reshape(B, S, cfg.n_heads,
                                           cfg.qk_nope_head_dim)
        v = (ckv @ p["w_uv"]).reshape(B, S, cfg.n_heads, cfg.v_head_dim)
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], -1)
        vp = jnp.concatenate(
            [v, jnp.zeros(v.shape[:-1] + (q.shape[-1] - v.shape[-1],),
                          v.dtype)], -1)
        out = attn_ops.flash_attention(q, k, vp, True, None, None,
                                       cfg.qk_head_dim ** -0.5)
        return out[..., : cfg.v_head_dim].reshape(B, S, -1) @ p["wo"]
    if S <= CHUNK_THRESHOLD:
        mask = jnp.broadcast_to(
            jnp.tril(jnp.ones((S, S), bool)), (B, S, S))
        return _attend(p, cfg, q_nope, q_rope, ckv, k_rope, mask)
    # q-chunked path: peak O(B*H*bq*S) score memory (32k prefill)
    c = CHUNK_Q
    assert S % c == 0, (S, c)
    nq = S // c
    qs = jnp.moveaxis(q_nope.reshape(B, nq, c, *q_nope.shape[2:]), 1, 0)
    qr = jnp.moveaxis(q_rope.reshape(B, nq, c, *q_rope.shape[2:]), 1, 0)
    kpos = jnp.arange(S)
    H = cfg.n_heads
    k_nope = (ckv @ p["w_uk"]).reshape(B, S, H, cfg.qk_nope_head_dim)
    v = (ckv @ p["w_uv"]).reshape(B, S, H, cfg.v_head_dim)

    def body(_, inp):
        i, qn_c, qr_c = inp
        qpos = i * c + jnp.arange(c)
        mask = jnp.broadcast_to((kpos[None, :] <= qpos[:, None]),
                                (B, c, S))
        return None, _attend(p, cfg, qn_c, qr_c, ckv, k_rope, mask,
                             kv=(k_nope, v))

    _, out = jax.lax.scan(body, None, (jnp.arange(nq), qs, qr))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, -1)


def init_cache(cfg: MLAConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    return {"ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, max_len, 1, cfg.qk_rope_head_dim), dtype),
            "len": jnp.zeros((), jnp.int32)}


def decode_step(p, cfg: MLAConfig, x, cache, *, eps=1e-6, **_):
    """One-token decode in latent space: x [B, 1, d]; returns
    (y [B, 1, d], cache').

    The query's no-rope part is carried into the latent by ``W_UK``
    (``q_lat`` [B, H, kv_lora_rank]); the scores are ``(q_lat . ckv +
    q_rope . k_rope) * qk_head_dim ** -0.5``, read straight from the
    cache as stored with f32 sums, then masked and an f32 softmax, as in
    ``_attend``; the output is ``(sum_t w_t ckv_t) W_UV``, then ``wo``.
    No per-position key or value of the cache is formed.

    Its device ops carry the named scopes ``decode.attention``
    (projections, absorbed query, scores, latent output) and
    ``decode.cache_write`` (the two latent writes)."""
    B = x.shape[0]
    t = cache["len"]
    H, r = cfg.n_heads, cfg.kv_lora_rank
    with jax.named_scope("decode.attention"):
        positions = jnp.full((B, 1), t, jnp.int32)
        q_nope, q_rope, ckv, k_rope = _latents(p, cfg, x, positions, eps)
    with jax.named_scope("decode.cache_write"):
        c2 = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), t, axis=1)
        r2 = jax.lax.dynamic_update_slice_in_dim(
            cache["kr"], k_rope.astype(cache["kr"].dtype), t, axis=1)
    with jax.named_scope("decode.attention"):
        w_uk = p["w_uk"].reshape(r, H, cfg.qk_nope_head_dim)
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
        f32 = jnp.float32
        logits = (jnp.einsum("bhr,btr->bht", q_lat.astype(c2.dtype), c2,
                             preferred_element_type=f32)
                  + jnp.einsum("bhd,btod->bht",
                               q_rope[:, 0].astype(r2.dtype), r2,
                               preferred_element_type=f32)) \
            * cfg.qk_head_dim ** -0.5
        S = c2.shape[1]
        logits = jnp.where((jnp.arange(S) <= t)[None, None, :], logits,
                           -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bht,btr->bhr", w.astype(c2.dtype), c2)
        w_uv = p["w_uv"].reshape(r, H, cfg.v_head_dim)
        out = jnp.einsum("bhr,rhd->bhd", o_lat.astype(x.dtype), w_uv)
        y = out.reshape(B, 1, H * cfg.v_head_dim) @ p["wo"]
    return y, {"ckv": c2, "kr": r2, "len": t + 1}
