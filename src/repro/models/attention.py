"""Multi-head attention: GQA/MQA, sliding window, logit softcap, qk_norm,
M-RoPE, cross-attention, KV-cache decode.

The score/softmax/value core routes through ``repro.kernels.attention.ops``
(Pallas flash kernel on TPU, jnp reference otherwise); everything around
it (projections, rope, cache) is plain jnp so XLA fuses it with the
surrounding block.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.common import (apply_mrope, apply_rope, attn_mask,
                                 dense_init, rmsnorm, shard_hint, softcap,
                                 split_keys)
from repro.models.config import AttnConfig


def init(key, cfg: AttnConfig, d_model: int) -> dict:
    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = split_keys(key, ["q", "k", "v", "o", "qn", "kn"])
    p = {
        "wq": dense_init(ks["q"], (d_model, H * D)),
        "wk": dense_init(ks["k"], (d_model, K * D)),
        "wv": dense_init(ks["v"], (d_model, K * D)),
        "wo": dense_init(ks["o"], (H * D, d_model)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((D,), jnp.bfloat16)
        p["k_norm"] = jnp.ones((D,), jnp.bfloat16)
    return p


def _project_qkv(p, cfg: AttnConfig, x, kv_src=None, *, positions=None,
                 eps=1e-6):
    """Returns q [B,Sq,H,D], k,v [B,Sk,K,D] with rope + qk_norm applied."""
    B, S, _ = x.shape
    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kv_in = x if kv_src is None else kv_src
    q = (x @ p["wq"]).reshape(B, S, H, D)
    k = (kv_in @ p["wk"]).reshape(B, kv_in.shape[1], K, D)
    v = (kv_in @ p["wv"]).reshape(B, kv_in.shape[1], K, D)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], eps)
        k = rmsnorm(k, p["k_norm"], eps)
    if not cfg.cross and cfg.use_rope:  # cross-attn keys carry no rope
        if cfg.mrope_sections is not None:
            q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    if k.shape[1] > 1:
        k = shard_hint(k, "kv_full")   # SP: keys gather the sequence
        v = shard_hint(v, "kv_full")
    return q, k, v


def core_attention(q, k, v, mask, *, cap=None, scale=None):
    """Reference core; [B,S,H,D] layout. Kernel-accelerated path lives in
    repro.kernels.attention (selected by the caller via use_kernel)."""
    H, K = q.shape[2], k.shape[2]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if H != K:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = shard_hint(logits, "attn_logits")
    if cap is not None:
        logits = softcap(logits, cap)
    logits = jnp.where(mask[:, None] if mask.ndim == 3 else mask,
                       logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)
    return out


CHUNK_THRESHOLD = 8192     # beyond this, q is processed in chunks
# 2048 amortizes the per-chunk k/v re-read + reduction passes; [B, H,
# 2048, Sk] f32 sharded over (data, model-on-Sq) stays ~1.3 GiB/device
# at the 32k cells (perf iteration 2, EXPERIMENTS.md §Perf)
CHUNK_Q = 2048


def _chunked_core(q, k, v, mpos, *, causal, window, cap, scale=None,
                  chunk=CHUNK_Q):
    """Q-chunked attention: full [bq, Sk] score rows per step, scanned
    over q chunks — peak memory O(B*H*bq*Sk) instead of O(B*H*S^2).
    The jnp analogue of the flash kernel's tiling, used where the Pallas
    path is off (CPU dry-run / non-TPU backends)."""
    B, S, H, D = q.shape
    nq = -(-S // chunk)
    pad = nq * chunk - S
    if pad:
        q = jnp.concatenate(
            [q, jnp.zeros((B, pad) + q.shape[2:], q.dtype)], axis=1)
        mpos = jnp.concatenate(
            [mpos, jnp.full(mpos.shape[:-1] + (pad,), -1, mpos.dtype)],
            axis=-1)
    qs = jnp.moveaxis(q.reshape(B, nq, chunk, H, D), 1, 0)
    qp = jnp.moveaxis(
        jnp.broadcast_to(mpos, (B, mpos.shape[-1]))
        .reshape(B, nq, chunk), 1, 0)
    kpos = jnp.broadcast_to(mpos[..., :1] * 0 + jnp.arange(k.shape[1]),
                            (B, k.shape[1]))

    def body(_, inp):
        qc, qpc = inp
        m = attn_mask(qpc, kpos, causal=causal, window=window)
        m &= (qpc >= 0)[..., None]
        return None, core_attention(qc, k, v, m, cap=cap, scale=scale)

    _, out = jax.lax.scan(body, None, (qs, qp))
    out = jnp.moveaxis(out, 0, 1).reshape(B, nq * chunk, H, D)
    return out[:, :S]


def forward(p, cfg: AttnConfig, x, *, positions, window=None,
            kv_src=None, eps=1e-6, use_kernel=False):
    """Full-sequence attention (training / prefill)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, kv_src, positions=positions, eps=eps)
    win = window if window is not None else cfg.window
    if cfg.cross:
        mask = jnp.ones((B, S, k.shape[1]), bool)
        out = core_attention(q, k, v, mask, cap=cfg.softcap)
    elif use_kernel and cfg.causal:
        from repro.kernels.attention import ops as attn_ops
        out = attn_ops.flash_attention(q, k, v, causal=True, window=win,
                                       softcap=cfg.softcap)
    else:
        # M-RoPE carries 3 position streams; masking uses the time stream
        mpos = positions[0] if cfg.mrope_sections is not None else positions
        if S > CHUNK_THRESHOLD:
            out = _chunked_core(q, k, v, mpos, causal=cfg.causal,
                                window=win, cap=cfg.softcap)
        else:
            mask = attn_mask(mpos, mpos, causal=cfg.causal, window=win)
            if mask.ndim == 2:
                mask = jnp.broadcast_to(mask, (B,) + mask.shape)
            else:
                mask = jnp.broadcast_to(mask, (B,) + mask.shape[1:])
            out = core_attention(q, k, v, mask, cap=cfg.softcap)
    return out.reshape(B, S, -1) @ p["wo"]


# ---------------------------------------------------------------------------
# decode with KV cache
# ---------------------------------------------------------------------------


def init_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    K, D = cfg.n_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, max_len, K, D), dtype),
            "v": jnp.zeros((batch, max_len, K, D), dtype),
            "len": jnp.zeros((), jnp.int32)}


def decode_core(q, ck, cv, mask, *, cap=None):
    """One query token against the whole cache, grouped by KV head.

    q [B, 1, H, D]; ck, cv [B, T, K, D] as stored; mask [B, 1, T].
    Query head ``h`` is ``(k, g) = (h // G, h % G)`` with ``G = H // K``,
    the head ``jnp.repeat(k, G, axis=2)`` pairs it with in
    ``core_attention``, so the cache is read once in its own layout and
    dtype: no repeated heads, no f32 copy.  Scores are summed in f32
    (a product of two bf16 values is exact in f32) and the softmax is f32,
    as in ``core_attention``.  Returns [B, 1, H * D]."""
    B, _, H, D = q.shape
    K = ck.shape[2]
    qg = q.reshape(B, K, H // K, D)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg, ck,
                        preferred_element_type=jnp.float32) * D ** -0.5
    if cap is not None:
        logits = softcap(logits, cap)
    logits = jnp.where(mask[:, :, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", w.astype(cv.dtype), cv)
    return out.reshape(B, 1, H * D)


def decode_step(p, cfg: AttnConfig, x, cache, *, window=None, eps=1e-6):
    """One-token decode: x [B, 1, d]; returns (y [B, 1, d], cache').

    Its device ops carry the named scopes ``decode.attention``
    (projections, the grouped scores and output of ``decode_core``) and
    ``decode.cache_write`` (the two cache updates)."""
    B = x.shape[0]
    t = cache["len"]
    with jax.named_scope("decode.attention"):
        positions = jnp.full((B, 1), t, jnp.int32)
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions[None], (3, B, 1))
        q, k, v = _project_qkv(p, cfg, x, positions=positions, eps=eps)
    with jax.named_scope("decode.cache_write"):
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, t, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, t, axis=1)
    with jax.named_scope("decode.attention"):
        S = ck.shape[1]
        kpos = jnp.arange(S)[None, :]
        win = window if window is not None else cfg.window
        mask = (kpos <= t)
        if win is not None:
            mask &= kpos > t - win
        mask = jnp.broadcast_to(mask[:, None, :], (B, 1, S))
        out = decode_core(q, ck, cv, mask, cap=cfg.softcap)
        y = out @ p["wo"]
    return y, {"k": ck, "v": cv, "len": t + 1}
