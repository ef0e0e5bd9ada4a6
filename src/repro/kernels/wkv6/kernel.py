"""Pallas TPU wkv6 kernel: chunked recurrence with VMEM-resident state.

The recurrence S_t = diag(w_t) S_{t-1} + k_t v_t^T is inherently
sequential in T, but the HBM traffic need not be: the grid walks
(batch*head, time-chunk) with the chunk axis sequential; the [N, N] f32
state lives in VMEM scratch across chunks, and each grid step streams
one [bt, N] tile of r/k/v/w through VMEM.  Per chunk the kernel runs the
bt inner steps as an unrolled loop of rank-1 updates + [N]x[N,N]
products on-chip — HBM sees each input element exactly once and the
state never spills (the memory-bound reference scan reloads S per step).

(The fully-matmul "intra-chunk attention" formulation trades this for
MXU utilization but needs per-channel exp rescaling that overflows for
fast-decay channels; the rank-1 form is exact — see DESIGN.md.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_scr, *, bt, nt):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[0].astype(jnp.float32)                       # [N]
    r = r_ref[0].astype(jnp.float32)                       # [bt, N]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)

    def step(i, carry):
        s, y = carry
        rt, kt, vt, wt = r[i], k[i], v[i], w[i]            # [N]
        kv = kt[:, None] * vt[None, :]                     # [N, N]
        yt = rt @ (s + u[:, None] * kv)                    # [N]
        s = wt[:, None] * s + kv
        y = jax.lax.dynamic_update_index_in_dim(y, yt, i, 0)
        return s, y

    s0 = s_scr[...]
    y0 = jnp.zeros((bt, r.shape[1]), jnp.float32)
    s, y = jax.lax.fori_loop(0, bt, step, (s0, y0))
    s_scr[...] = s
    y_ref[0, ...] = y.astype(y_ref.dtype)


def wkv6_bhtn(r, k, v, w, u, *, block_t=64, interpret=False):
    """r,k,v,w [BH, T, N]; u [BH, N] -> y [BH, T, N] float32."""
    BH, T, N = r.shape
    bt = min(block_t, T)
    assert T % bt == 0, (T, bt)
    nt = T // bt
    kern = functools.partial(_kernel, bt=bt, nt=nt)
    return pl.pallas_call(
        kern,
        grid=(BH, nt),
        in_specs=[
            pl.BlockSpec((1, bt, N), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, bt, N), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, bt, N), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, bt, N), lambda h, t: (h, t, 0)),
            pl.BlockSpec((1, N), lambda h, t: (h, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, N), lambda h, t: (h, t, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, N), jnp.float32),
        scratch_shapes=[_vmem((N, N), jnp.float32)],
        compiler_params=_tpu_params(),
        interpret=interpret,
    )(r, k, v, w, u)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _tpu_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
