"""Pallas TPU flash attention (online softmax, GQA, causal/window,
logit softcap).

Tiling: grid = (B * H, nQ, nK); the kv axis is the innermost sequential
dimension ("arbitrary"), so the [bq, D] f32 accumulator and the running
(max, sum) statistics live in VMEM scratch across kv steps and flush to
the output block on the last step.  Q/K/V tiles stream HBM -> VMEM per
step; D is kept whole (128/256 — MXU-aligned) and bq/bk default to 128
lanes/sublanes-aligned tiles.

GQA is expressed in the index_map: kv head index = query head // group
size, so no repeated KV materializes in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
            scale, causal, window, softcap, bq, bk, nk):
    j = pl.program_id(2)    # kv block
    i = pl.program_id(1)    # q block

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0].astype(jnp.float32) * scale            # [bq, D]
    k = k_ref[0].astype(jnp.float32)                    # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_s[:, 0], l_s[:, 0]
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_cur[:, None])
    alpha = jnp.exp(m_prev - m_cur)
    l_cur = alpha * l_prev + p.sum(axis=1)
    acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())))
    m_s[:, 0], l_s[:, 0] = m_cur, l_cur

    @pl.when(j == nk - 1)
    def _flush():
        # rows with no live kv (fully masked) produce 0, not NaN
        denom = jnp.where(l_s[:, 0] > 0, l_s[:, 0], 1.0)
        o_ref[0, ...] = (acc[...] / denom[:, None]).astype(o_ref.dtype)


def _kernel_gather(idx_ref, q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s,
                   qg, live_s, *, scale, causal, window, softcap, bq, bk,
                   nk):
    """Dispatch-gather prologue: the q tile is assembled IN VMEM from a
    token-order q buffer via per-output row indices (``-1`` -> zero
    row) — the terminal gather round of an alltoall-style dispatch
    fused into the attention kernel, so the permuted q tensor never
    materializes in HBM.  Positions (causal/window masks) are
    output-order.

    The gather runs once per q block (first kv step) as a one-hot
    matmul on the MXU, ``onehot[bq, Sq] @ q[Sq, D]``, into the ``qg``
    scratch; a dead row's one-hot row is all zero.  Each output row
    picks exactly one q row, so the product is an exact copy (bf16 q:
    f32 accumulation; f32 q: HIGHEST precision) as long as q holds no
    inf/NaN, which a zero one-hot entry would spread."""
    j = pl.program_id(2)    # kv block
    i = pl.program_id(1)    # q block

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        idx = idx_ref[0]                                # [bq, 1] int32
        qfull = q_ref[0]                                # [Sq, D]
        sq = qfull.shape[0]
        onehot = (idx == jax.lax.broadcasted_iota(jnp.int32, (bq, sq), 1)
                  ).astype(qfull.dtype)                 # [bq, Sq]
        prec = (jax.lax.Precision.HIGHEST if qfull.dtype == jnp.float32
                else None)
        qg[...] = jax.lax.dot_general(
            onehot, qfull, (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * scale  # [bq, D]
        live_s[...] = (idx >= 0).astype(jnp.float32)

    live = live_s[...] > 0                              # [bq, 1]
    q = qg[...]
    k = k_ref[0].astype(jnp.float32)                    # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.broadcast_to(live, (bq, bk))
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_s[:, 0], l_s[:, 0]
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_cur[:, None])
    alpha = jnp.exp(m_prev - m_cur)
    l_cur = alpha * l_prev + p.sum(axis=1)
    acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())))
    m_s[:, 0], l_s[:, 0] = m_cur, l_cur

    @pl.when(j == nk - 1)
    def _flush():
        # dead rows (idx -1): every kv position was masked, so the
        # running max never left NEG_INF and p degenerated to exp(0) —
        # the accumulator holds garbage there; zero it at the write.
        denom = jnp.where(l_s[:, 0] > 0, l_s[:, 0], 1.0)
        out = acc[...] / denom[:, None]
        o_ref[0, ...] = jnp.where(live, out, 0.0).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal=True, window=None,
                         softcap=None, scale=None, block_q=128,
                         block_k=128, interpret=False, q_rows=None,
                         nheads=None):
    """q [BH, Sq, D], k/v [BK, Sk, D]; BH = BK * group -> out like q.

    ``q_rows`` [B, Sq] (int32, requires ``nheads`` with BH = B * nheads)
    turns on the dispatch-gather prologue: output row t of batch b
    attends with row ``q_rows[b, t]`` of the token-order q buffer
    (``-1`` -> zero row, output row is 0)."""
    BH, Sq, D = q.shape
    BK, Sk, _ = k.shape
    assert BH % BK == 0
    group = BH // BK
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nk = Sk // bk
    scale = scale if scale is not None else D ** -0.5
    grid = (BH, Sq // bq, nk)
    kv_specs = [
        pl.BlockSpec((1, bk, D), lambda h, i, j, g=group: (h // g, j, 0)),
        pl.BlockSpec((1, bk, D), lambda h, i, j, g=group: (h // g, j, 0)),
    ]
    out_spec = pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0))
    scratch = [
        _vmem((bq, D), jnp.float32),
        _vmem((bq, 1), jnp.float32),
        _vmem((bq, 1), jnp.float32),
    ]
    if q_rows is None:
        kern = functools.partial(_kernel, scale=scale, causal=causal,
                                 window=window, softcap=softcap,
                                 bq=bq, bk=bk, nk=nk)
        return pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
                      *kv_specs],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
            scratch_shapes=scratch,
            compiler_params=_tpu_params(),
            interpret=interpret,
        )(q, k, v)
    assert nheads is not None and BH % nheads == 0, (BH, nheads)
    assert q_rows.shape == (BH // nheads, Sq), (q_rows.shape, Sq)
    kern = functools.partial(_kernel_gather, scale=scale, causal=causal,
                             window=window, softcap=softcap,
                             bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            # idx column for this q block, shared by the batch's heads
            pl.BlockSpec((1, bq, 1), lambda h, i, j, nh=nheads: (h // nh,
                                                                 i, 0)),
            # the FULL token-order q row buffer for this head
            pl.BlockSpec((1, Sq, D), lambda h, i, j: (h, 0, 0)),
            *kv_specs,
        ],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=scratch + [_vmem((bq, D), jnp.float32),
                                  _vmem((bq, 1), jnp.float32)],
        compiler_params=_tpu_params(),
        interpret=interpret,
    )(q_rows.astype(jnp.int32)[..., None], q, k, v)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _tpu_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
