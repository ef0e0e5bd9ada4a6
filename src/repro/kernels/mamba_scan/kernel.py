"""Pallas TPU selective-scan (Mamba-1) kernel.

Same shape of argument as the wkv6 kernel: the recurrence is sequential
in T but the per-step temporaries (dA, dBx — [Di, S] floats each) never
need to exist in HBM.  Grid = (B * Di-blocks, T-chunks) with the chunk
axis sequential; the [bdi, S] f32 state lives in VMEM scratch across
chunks, inputs stream one [bt, bdi] / [bt, S] tile per step, and only y
is written back.  HBM traffic drops from O(T * Di * S) to O(T * (Di + S))
— the memory-roofline fix for the jamba train cells (§Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(xc_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_scr, *,
            bt):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    xc = xc_ref[0].astype(jnp.float32)        # [bt, bdi]
    dt = dt_ref[0].astype(jnp.float32)
    bmat = b_ref[0].astype(jnp.float32)       # [bt, S]
    cmat = c_ref[0].astype(jnp.float32)
    A = a_ref[...].astype(jnp.float32)        # [bdi, S]
    D = d_ref[...].astype(jnp.float32)        # [bdi]

    def step(i, carry):
        h, y = carry
        dA = jnp.exp(dt[i][:, None] * A)                    # [bdi, S]
        dBx = (dt[i] * xc[i])[:, None] * bmat[i][None, :]
        h = dA * h + dBx
        yt = h @ cmat[i] + D * xc[i]                        # [bdi]
        y = jax.lax.dynamic_update_index_in_dim(y, yt, i, 0)
        return h, y

    h0 = h_scr[...]
    y0 = jnp.zeros((bt, xc.shape[1]), jnp.float32)
    h, y = jax.lax.fori_loop(0, bt, step, (h0, y0))
    h_scr[...] = h
    y_ref[0, ...] = y.astype(y_ref.dtype)


def selective_scan_bdt(xc, dt, bmat, cmat, A, D, *, block_t=64,
                       block_di=None, interpret=False):
    """xc, dt: [B, T, Di]; bmat, cmat: [B, T, S]; A: [Di, S]; D: [Di].
    Returns y [B, T, Di] f32."""
    B_, T, Di = xc.shape
    S = bmat.shape[-1]
    bt = min(block_t, T)
    assert T % bt == 0
    bdi = block_di or min(Di, 512)
    while Di % bdi:
        bdi //= 2
    n_di = Di // bdi
    grid = (B_ * n_di, T // bt)
    kern = functools.partial(_kernel, bt=bt)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bdi),
                         lambda i, t, n=n_di: (i // n, t, i % n)),
            pl.BlockSpec((1, bt, bdi),
                         lambda i, t, n=n_di: (i // n, t, i % n)),
            pl.BlockSpec((1, bt, S),
                         lambda i, t, n=n_di: (i // n, t, 0)),
            pl.BlockSpec((1, bt, S),
                         lambda i, t, n=n_di: (i // n, t, 0)),
            pl.BlockSpec((bdi, S), lambda i, t, n=n_di: (i % n, 0)),
            pl.BlockSpec((bdi,), lambda i, t, n=n_di: (i % n,)),
        ],
        out_specs=pl.BlockSpec((1, bt, bdi),
                               lambda i, t, n=n_di: (i // n, t, i % n)),
        out_shape=jax.ShapeDtypeStruct((B_, T, Di), jnp.float32),
        scratch_shapes=[_vmem((bdi, S), jnp.float32)],
        compiler_params=_tpu_params(),
        interpret=interpret,
    )(xc, dt, bmat, cmat, A, D)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _tpu_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
