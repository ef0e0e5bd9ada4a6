"""jit'd public wrapper for the selective-scan kernel (custom VJP via
reference recompute; interpret mode on CPU).

REPRO_KERNEL_SURROGATE=1 (set only by the dry-run) swaps the kernel for
an HBM-traffic-equivalent stand-in — reads every input once, writes the
output once, no recurrence internals — so the CPU dry-run measures the
kernel path's memory signature without lowering Pallas to CPU.  Values
are wrong; the dry-run never executes, only compiles.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels.compat import pallas_interpret
from repro.kernels.mamba_scan.kernel import selective_scan_bdt
from repro.kernels.mamba_scan.ref import selective_scan_ref


def _surrogate(xc, dt, bmat, cmat, A, D):
    red = (bmat.astype(jnp.float32).sum(-1, keepdims=True)
           + cmat.astype(jnp.float32).sum(-1, keepdims=True))
    return (xc.astype(jnp.float32) * dt.astype(jnp.float32) + red) \
        * (A.sum() + D)


def selective_scan(xc, dt, bmat, cmat, A, D, block_t=64):
    if (os.environ.get("REPRO_KERNEL_SURROGATE") == "1"
            and pallas_interpret()):
        # differentiable surrogate: its AD transpose streams the same
        # tensors a fused backward kernel would (inputs + grads once)
        return _surrogate(xc, dt, bmat, cmat, A, D)
    return _scan_vjp(xc, dt, bmat, cmat, A, D, block_t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan_vjp(xc, dt, bmat, cmat, A, D, block_t=64):
    return selective_scan_bdt(xc, dt, bmat, cmat, A, D, block_t=block_t,
                              interpret=pallas_interpret())


def _fwd(xc, dt, bmat, cmat, A, D, block_t):
    return (_scan_vjp(xc, dt, bmat, cmat, A, D, block_t),
            (xc, dt, bmat, cmat, A, D))


def _bwd(block_t, res, g):
    _, vjp = jax.vjp(lambda *a: selective_scan_ref(*a)[0], *res)
    return vjp(g)


_scan_vjp.defvjp(_fwd, _bwd)
