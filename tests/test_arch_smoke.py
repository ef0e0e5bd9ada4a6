"""Per-architecture smoke tests: reduced same-family configs, one
forward + one grad (train) step + one decode step on CPU; asserts output
shapes and finiteness.  Full configs are exercised only via the dry-run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs import shapes as shp
from repro.models import model as M

B, S = 2, 16


def _inputs(cfg, key):
    ks = jax.random.split(key, 3)
    tokens = jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)
    kw = {}
    if cfg.encoder is not None:
        kw["encoder_frames"] = jax.random.normal(
            ks[1], (B, cfg.encoder.n_frames, cfg.encoder.d_model),
            jnp.bfloat16)
    if cfg.vision_prefix:
        kw["vision_embeds"] = jax.random.normal(
            ks[2], (B, cfg.vision_prefix, cfg.d_model), jnp.bfloat16)
    return tokens, kw


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_forward_and_grad(arch):
    cfg = configs.get_smoke(arch)
    key = jax.random.key(0)
    params = M.init_params(key, cfg)
    tokens, kw = _inputs(cfg, jax.random.key(1))
    logits = jax.jit(lambda p: M.forward(p, cfg, tokens, **kw))(params)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    labels = jnp.roll(tokens, -1, axis=1)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: M.lm_loss(p, cfg, tokens, labels, **kw)))(params)
    assert np.isfinite(float(loss))
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_decode_step(arch):
    cfg = configs.get_smoke(arch)
    params = M.init_params(jax.random.key(0), cfg)
    cache = M.init_cache(cfg, batch=B, max_len=S)
    tokens, kw = _inputs(cfg, jax.random.key(1))
    cross = (M.encode(params, cfg, kw["encoder_frames"])
             if cfg.encoder is not None else None)

    step = jax.jit(lambda c, t: M.decode_step(params, cfg, c, t,
                                              cross_src=cross))
    logits, cache = step(cache, tokens[:, :1])
    assert logits.shape == (B, 1, cfg.vocab_size)
    logits, cache = step(cache, tokens[:, 1:2])
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", ["rwkv6-3b", "jamba-1.5-large-398b"])
def test_recurrent_decode_matches_forward(arch):
    """O(1)-state decode must reproduce the parallel forward logits —
    the property that makes the 500k cell runnable for SSM/hybrid."""
    cfg = configs.get_smoke(arch)
    params = M.init_params(jax.random.key(0), cfg)
    tokens, _ = _inputs(cfg, jax.random.key(1))
    full = M.forward(params, cfg, tokens).astype(jnp.float32)

    cache = M.init_cache(cfg, batch=B, max_len=S)
    step = jax.jit(lambda c, t: M.decode_step(params, cfg, c, t))
    outs = []
    for i in range(S):
        lg, cache = step(cache, tokens[:, i: i + 1])
        outs.append(np.asarray(lg[:, 0], np.float32))
    dec = np.stack(outs, axis=1)
    diff = np.abs(dec - np.asarray(full))
    if cfg.moe is not None:
        # forward uses dense-dispatch MoE, decode uses capacity dispatch:
        # near-tied top-k routing can flip between them under bf16, so a
        # small fraction of logits legitimately diverges.  Assert the
        # bulk agrees and the decoded distribution is operationally the
        # same (top-1 agreement).
        assert np.quantile(diff, 0.9) < 0.11, np.quantile(diff, 0.9)
        top_full = np.asarray(full).argmax(-1)
        top_dec = dec.argmax(-1)
        agree = (top_full == top_dec).mean()
        assert agree >= 0.9, agree
    else:
        np.testing.assert_allclose(dec, np.asarray(full), atol=0.11,
                                   rtol=0.05)


def test_full_param_counts():
    """Full configs hit their published parameter classes (eval_shape
    only — no allocation)."""
    expect = {
        "gemma2-2b": (2.0e9, 3.5e9),
        "gemma-2b": (2.0e9, 3.3e9),
        "qwen3-14b": (13e9, 16e9),
        "smollm-360m": (0.3e9, 0.45e9),
        "deepseek-v3-671b": (630e9, 700e9),
        # Moonlight-16B-A3B as published: 15.96B
        "moonshot-v1-16b-a3b": (15.5e9, 16.5e9),
        "rwkv6-3b": (2.7e9, 3.6e9),
        "whisper-small": (0.2e9, 0.3e9),
        "qwen2-vl-7b": (6.5e9, 8.5e9),
        "jamba-1.5-large-398b": (370e9, 420e9),
    }
    for arch, (lo, hi) in expect.items():
        n = configs.get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}," \
                              f" {hi/1e9}]B"


def test_cells_applicability():
    cells = shp.cells()
    assert len(cells) == 40
    skips = [(a, s) for a, s, ok in cells if not ok]
    assert len(skips) == 8
    assert all(s == "long_500k" for _, s in skips)
