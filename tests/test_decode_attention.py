"""The one-token decode attention core (``attention.decode_core``).

It must give what ``core_attention`` gives for the same query, cache and
mask, bit for bit on the CPU: the scores are the same f32 sums of exact
bf16 products, and the softmax and the output's dtype are the same.  And
the lowered decode step must read the bf16 cache as stored: no heads
repeated to H, no f32 copy of the cache.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention

B, T, D = 3, 20, 16


def _decode_mask(t, window):
    kpos = jnp.arange(T)[None, :]
    mask = kpos <= t
    if window is not None:
        mask &= kpos > t - window
    return jnp.broadcast_to(mask[:, None, :], (B, 1, T))


@pytest.mark.parametrize("t", [0, T // 2, T - 1])
@pytest.mark.parametrize("cap", [None, 30.0])
@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("heads", [(15, 5), (8, 1), (4, 4)],
                         ids=["gqa", "mqa", "mha"])
def test_decode_core_matches_core_attention(heads, window, cap, t):
    H, K = heads
    kq, kk, kv = jax.random.split(jax.random.key(H * 100 + t), 3)
    q = jax.random.normal(kq, (B, 1, H, D)).astype(jnp.bfloat16)
    ck = jax.random.normal(kk, (B, T, K, D)).astype(jnp.bfloat16)
    cv = jax.random.normal(kv, (B, T, K, D)).astype(jnp.bfloat16)
    mask = _decode_mask(t, window)
    want = jax.jit(lambda *a: attention.core_attention(*a, cap=cap))(
        q, ck, cv, mask).reshape(B, 1, H * D)
    got = jax.jit(lambda *a: attention.decode_core(*a, cap=cap))(
        q, ck, cv, mask)
    assert got.dtype == want.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


_OP = re.compile(r"stablehlo\.(broadcast_in_dim|convert)\b.*->\s*"
                 r"tensor<([0-9x]+)x(\w+)>")


def _ops(text):
    """(op, result shape, result dtype) of every broadcast and convert."""
    out = []
    for line in text.splitlines():
        m = _OP.search(line)
        if m:
            shape = tuple(int(n) for n in m.group(2).split("x"))
            out.append((m.group(1), shape, m.group(3)))
    return out


def _offending(ops, cache_elems, repeated):
    return [o for o in ops
            if (o[0] == "broadcast_in_dim" and o[1] in repeated)
            or (o[0] == "convert" and o[2] == "f32"
                and int(np.prod(o[1])) >= cache_elems)]


def test_decode_step_reads_cache_without_repeat_or_f32_copy():
    """smollm-360m's widths: batch 32, cache 1280, 15 query heads over
    5 KV heads of 64.  ``core_attention`` on the same operands is the
    control: its lowering has both patterns, so the matcher is live."""
    cfg = configs.get_config("smollm-360m")
    a = cfg.attn
    Bs, S, H, K, Dh = 32, 1280, a.n_heads, a.n_kv_heads, a.head_dim
    G = H // K
    repeated = {(Bs, S, K, G, Dh), (Bs, S, H, Dh)}
    cache_elems = Bs * S * K * Dh
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(
        lambda: attention.init(jax.random.key(0), a, cfg.d_model))
    cache = {"k": sds((Bs, S, K, Dh), jnp.bfloat16),
             "v": sds((Bs, S, K, Dh), jnp.bfloat16),
             "len": sds((), jnp.int32)}
    x = sds((Bs, 1, cfg.d_model), jnp.bfloat16)
    step = jax.jit(lambda p, x, c: attention.decode_step(p, a, x, c))
    ops = _ops(step.lower(params, x, cache).as_text())
    assert _offending(ops, cache_elems, repeated) == []

    q = sds((Bs, 1, H, Dh), jnp.bfloat16)
    kv = cache["k"]
    mask = sds((Bs, 1, S), jnp.bool_)
    control = _ops(jax.jit(attention.core_attention)
                   .lower(q, kv, kv, mask).as_text())
    bad = _offending(control, cache_elems, repeated)
    assert {o[0] for o in bad} == {"broadcast_in_dim", "convert"}
