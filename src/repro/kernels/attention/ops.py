"""jit'd public wrapper for the flash-attention kernel.

Layout adaptation [B,S,H,D] <-> [B*H,S,D], GQA head mapping, custom VJP
(forward = kernel; backward = recompute via the jnp reference — same
math, so gradients are exact up to dtype rounding), and automatic
interpret-mode on CPU so every test/benchmark runs here while the same
code path targets TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.attention.kernel import flash_attention_bhsd
from repro.kernels.attention.ref import attention_ref
from repro.kernels.compat import pallas_interpret


def _to_bhsd(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _from_bhsd(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _surrogate(q, k, v):
    """HBM-traffic-equivalent stand-in (REPRO_KERNEL_SURROGATE dry-run
    only): streams q/k/v once, writes out once — the flash kernel's
    memory signature, no [Sq, Sk] logits in HBM."""
    import jax.numpy as jnp
    B, Sq, H, D = q.shape
    K = k.shape[2]
    km = k.astype(jnp.float32).mean(1, keepdims=True)   # [B,1,K,D]
    vm = v.astype(jnp.float32).mean(1, keepdims=True)
    mix = (km + vm).repeat(H // K, axis=2)              # [B,1,H,D]
    return (q.astype(jnp.float32) + mix).astype(q.dtype)


def flash_attention(q, k, v, causal=True, window=None, softcap=None,
                    scale=None, block_q=128, block_k=128, q_rows=None):
    """q [B,Sq,H,D], k/v [B,Sk,K,D] -> [B,Sq,H,D] (flash kernel).

    ``q_rows`` ([Sq] or [B, Sq] int32) fuses a dispatch-gather prologue
    into the kernel: output row t attends with token-order q row
    ``q_rows[..., t]`` (``-1`` -> zero output row), so the permuted q of
    an alltoall-style dispatch never materializes in HBM.  Causal /
    window positions are output-order.  The gather is a one-hot matmul
    on the MXU, ``[block_q, Sq] @ [Sq, D]`` per q block: about
    ``2 * Sq * Sq * D`` more flops per head, as much again as QK^T.  It
    copies rows exactly only while q is finite: an inf or NaN in any q
    row of a batch turns every gathered row of that batch into NaN
    (0 * inf)."""
    import os
    if (os.environ.get("REPRO_KERNEL_SURROGATE") == "1"
            and pallas_interpret()):
        # differentiable surrogate (dry-run): fwd+bwd stream q/k/v/grads
        # once — the flash fwd+bwd kernels' HBM signature
        return _surrogate(q, k, v)
    if q_rows is not None:
        if q_rows.ndim == 1:
            q_rows = jnp.broadcast_to(q_rows[None], (q.shape[0],)
                                      + q_rows.shape)
        return _flash_gather_vjp(q, k, v, q_rows, causal, window,
                                 softcap, scale, block_q, block_k)
    return _flash_vjp(q, k, v, causal, window, softcap, scale, block_q,
                      block_k)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_vjp(q, k, v, causal=True, window=None, softcap=None,
               scale=None, block_q=128, block_k=128):
    B, Sq, H, D = q.shape
    bq = min(block_q, Sq)
    bk = min(block_k, Sq if k is None else k.shape[1])
    out = flash_attention_bhsd(
        _to_bhsd(q), _to_bhsd(k), _to_bhsd(v), causal=causal,
        window=window, softcap=softcap, scale=scale,
        block_q=bq, block_k=bk, interpret=pallas_interpret())
    return _from_bhsd(out, B, H)


def _fwd(q, k, v, causal, window, softcap, scale, block_q, block_k):
    out = _flash_vjp(q, k, v, causal, window, softcap, scale,
                     block_q, block_k)
    return out, (q, k, v)


def _bwd(causal, window, softcap, scale, block_q, block_k, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=causal,
                                         window=window, softcap=softcap,
                                         scale=scale), q, k, v)
    return vjp(g)


_flash_vjp.defvjp(_fwd, _bwd)


def gathered_attention_ref(q, k, v, q_rows, *, causal=True, window=None,
                           softcap=None, scale=None):
    """Oracle for the gather-prologue kernel: explicit jnp gather of the
    token-order q rows (``-1`` -> zero row), then the plain reference;
    fully-dead output rows are zeroed like the kernel's flush."""
    live = q_rows >= 0                                  # [B, Sq]
    safe = jnp.where(live, q_rows, 0)
    qg = jnp.take_along_axis(q, safe[..., None, None], axis=1)
    qg = jnp.where(live[..., None, None], qg, 0)
    out = attention_ref(qg, k, v, causal=causal, window=window,
                        softcap=softcap, scale=scale)
    return jnp.where(live[..., None, None], out, 0)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_gather_vjp(q, k, v, q_rows, causal=True, window=None,
                      softcap=None, scale=None, block_q=128,
                      block_k=128):
    B, Sq, H, D = q.shape
    bq = min(block_q, Sq)
    bk = min(block_k, k.shape[1])
    out = flash_attention_bhsd(
        _to_bhsd(q), _to_bhsd(k), _to_bhsd(v), causal=causal,
        window=window, softcap=softcap, scale=scale,
        block_q=bq, block_k=bk, interpret=pallas_interpret(),
        q_rows=q_rows, nheads=H)
    return _from_bhsd(out, B, H)


def _gather_fwd(q, k, v, q_rows, causal, window, softcap, scale,
                block_q, block_k):
    out = _flash_gather_vjp(q, k, v, q_rows, causal, window, softcap,
                            scale, block_q, block_k)
    return out, (q, k, v, q_rows)


def _gather_bwd(causal, window, softcap, scale, block_q, block_k,
                res, g):
    q, k, v, q_rows = res
    # the gather is part of the differentiated graph, so d/dq is the
    # scatter-add of the gathered-row grads back to token order
    _, vjp = jax.vjp(
        lambda q_, k_, v_: gathered_attention_ref(
            q_, k_, v_, q_rows, causal=causal, window=window,
            softcap=softcap, scale=scale), q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None


_flash_gather_vjp.defvjp(_gather_fwd, _gather_bwd)
