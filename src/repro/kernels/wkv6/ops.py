"""jit'd public wrapper for the wkv6 kernel ([B,T,H,N] layout, custom
VJP via reference recompute, interpret mode on CPU)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.compat import pallas_interpret
from repro.kernels.wkv6.kernel import wkv6_bhtn
from repro.kernels.wkv6.ref import wkv6_ref


def wkv6(r, k, v, w, u, block_t=64):
    """r,k,v,w [B,T,H,N]; u [H,N] -> y [B,T,H,N] float32."""
    import os
    if (os.environ.get("REPRO_KERNEL_SURROGATE") == "1"
            and pallas_interpret()):
        # differentiable HBM-traffic stand-in (dry-run only): fwd+bwd
        # stream inputs/grads once — state stays in VMEM.
        return (r.astype(jnp.float32) * k.astype(jnp.float32)
                + v.astype(jnp.float32) * w.astype(jnp.float32) + u)
    return _wkv_vjp(r, k, v, w, u, block_t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _wkv_vjp(r, k, v, w, u, block_t=64):
    B, T, H, N = r.shape
    to = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, N)
    ub = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, N)
    y = wkv6_bhtn(to(r), to(k), to(v), to(w), ub,
                  block_t=block_t, interpret=pallas_interpret())
    return y.reshape(B, H, T, N).transpose(0, 2, 1, 3)


def _fwd(r, k, v, w, u, block_t):
    return _wkv_vjp(r, k, v, w, u, block_t), (r, k, v, w, u)


def _bwd(block_t, res, g):
    r, k, v, w, u = res
    _, vjp = jax.vjp(lambda *a: wkv6_ref(*a)[0], r, k, v, w, u)
    return vjp(g)


_wkv_vjp.defvjp(_fwd, _bwd)
