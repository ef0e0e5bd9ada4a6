"""A run whose timed path is broken underneath comes out not correct:
the harness's whole run at a tiny size on the CPU, with one fault
planted in the program for each kind of cell."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench import harness


def run(tree, cell, seed=3):
    return harness.run(cell, seed, 0.3, False, root=tree,
                       on_accelerator=False, log=lambda s: 0)


@pytest.mark.parametrize("cell", ["tiny.train.1chip", "tiny.train.dp4",
                                  "tiny.decode"])
def test_sound_runs_are_correct(tree, cell):
    out = run(tree, cell)
    assert out["correct"], out["checks"]


def _wrap_step(monkeypatch, wrap):
    import repro.train.step as step_mod
    make = step_mod.make_train_step
    monkeypatch.setattr(step_mod, "make_train_step",
                        lambda *a, **k: wrap(make(*a, **k)))


def test_a_step_that_returns_its_state_unchanged(tree, monkeypatch):
    def wrap(step):
        def broken(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return broken
    _wrap_step(monkeypatch, wrap)
    out = run(tree, "tiny.train.1chip")
    assert not out["correct"]
    assert out["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out(tree, monkeypatch):
    def wrap(step):
        def broken(state, batch):
            b = batch["labels"].shape[0]
            rows = jnp.arange(b)[:, None] < b // 2
            return step(state, dict(batch, labels=jnp.where(
                rows, batch["labels"], -100)))
        return broken
    _wrap_step(monkeypatch, wrap)
    out = run(tree, "tiny.train.1chip")
    assert not out["correct"], out["checks"]


def test_the_gradient_exchange_left_out(tree, monkeypatch):
    import repro.train.sync as sync
    monkeypatch.setattr(
        sync, "dp_allreduce",
        lambda grads, axes, denom=None, **k: jax.tree.map(
            lambda g: (g / denom).astype(g.dtype), grads))
    out = run(tree, "tiny.train.dp4")
    assert not out["correct"], out["checks"]


def test_a_served_token_altered(tree, monkeypatch):
    import repro.serve.step as serve
    make = serve.make_decode_step

    def broken(cfg, mesh, opts):
        step = make(cfg, mesh, opts)

        def decode(params, cache, tokens):
            nxt, cache = step(params, cache, tokens)
            return (nxt + 1) % cfg.vocab_size, cache
        return decode
    monkeypatch.setattr(serve, "make_decode_step", broken)
    out = run(tree, "tiny.decode")
    assert not out["correct"], out["checks"]
