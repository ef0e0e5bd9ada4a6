"""Substrate tests: optimizer, compression, data pipeline determinism,
atomic/async checkpointing, fault-tolerant loop, straggler rebalance,
elastic remesh."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # dev extra not installed: seeded fallback
    from _hypothesis_stub import given, settings, st

import jax
import jax.numpy as jnp

from repro.optim import (adamw_init, adamw_update, clip_by_global_norm,
                         compress_int8, decompress_int8, cosine_schedule)
from repro.optim.compress import ef_compress_tree
from repro.data import DataPipeline, PipelineConfig
from repro.checkpoint import (AsyncCheckpointer, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.runtime import (FaultTolerantLoop, PreemptionSignal,
                           StragglerMonitor, remesh_plan)


# ---------------------------------------------------------------------------
# optim
# ---------------------------------------------------------------------------


def test_adamw_reduces_quadratic():
    p = {"w": jnp.array([3.0, -2.0], jnp.float32)}
    st_ = adamw_init(p)
    lr = 0.1
    for _ in range(200):
        g = {"w": 2 * p["w"]}
        p, st_ = adamw_update(p, g, st_, lr=lr, weight_decay=0.0)
    assert float(jnp.abs(p["w"]).max()) < 1e-2


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0), "b": jnp.full((3,), -10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    total = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                         for x in jax.tree.leaves(clipped)))
    assert float(norm) == pytest.approx(np.sqrt(700.0), rel=1e-5)
    assert float(total) == pytest.approx(1.0, rel=1e-4)


def test_cosine_schedule_shape():
    assert float(cosine_schedule(jnp.int32(0), peak_lr=1e-3,
                                 warmup_steps=10, total_steps=100)) == 0.0
    peak = float(cosine_schedule(jnp.int32(10), peak_lr=1e-3,
                                 warmup_steps=10, total_steps=100))
    assert peak == pytest.approx(1e-3, rel=1e-5)
    end = float(cosine_schedule(jnp.int32(100), peak_lr=1e-3,
                                warmup_steps=10, total_steps=100))
    assert end == pytest.approx(1e-4, rel=1e-3)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31), n=st.integers(1, 2000))
def test_int8_roundtrip_error_bound(seed, n):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n,)).astype(np.float32) * rng.uniform(0.1, 10)
    q, s = compress_int8(jnp.asarray(x))
    back = np.asarray(decompress_int8(q, s, (n,), jnp.float32))
    # absmax-block int8: error <= scale/2 per element
    scale = np.repeat(np.asarray(s), 256)[:n]
    assert (np.abs(back - x) <= scale / 2 + 1e-6).all()


def test_error_feedback_unbiased_over_steps():
    """EF residual keeps the *accumulated* quantization error bounded, so
    the mean applied gradient converges to the true mean."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=(512,)).astype(np.float32))
    res = None
    applied = np.zeros(512, np.float32)
    T = 64
    for _ in range(T):
        comp_tree, res = ef_compress_tree({"g": g_true}, res)
        q, s = comp_tree["g"]
        applied += np.asarray(decompress_int8(q, s, (512,), jnp.float32))
    err = np.abs(applied / T - np.asarray(g_true)).max()
    assert err < 0.05 * float(jnp.abs(g_true).max())


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def test_pipeline_deterministic_and_shard_consistent():
    cfg = PipelineConfig(vocab_size=1000, seq_len=64, global_batch=8)
    full = DataPipeline(cfg, 1, 0)
    b0 = full.batch(7)
    again = DataPipeline(cfg, 1, 0).batch(7)
    np.testing.assert_array_equal(b0["tokens"], again["tokens"])
    # sharded views tile the global batch exactly
    parts = [DataPipeline(cfg, 4, k).batch(7)["tokens"] for k in range(4)]
    np.testing.assert_array_equal(np.concatenate(parts), b0["tokens"])
    # different steps differ
    assert not np.array_equal(full.batch(8)["tokens"], b0["tokens"])


def test_pipeline_labels_shifted_and_masked():
    cfg = PipelineConfig(vocab_size=1000, seq_len=128, global_batch=2,
                         mean_doc_len=16)
    b = DataPipeline(cfg).batch(0)
    toks, labels = np.asarray(b["tokens"]), np.asarray(b["labels"])
    live = labels[:, :-1] >= 0
    np.testing.assert_array_equal(labels[:, :-1][live],
                                  toks[:, 1:][live])
    assert (labels[:, -1] == -100).all()
    # boundaries exist and are masked
    assert (labels == -100).sum() > 2


def test_pipeline_reshard_preserves_stream():
    cfg = PipelineConfig(vocab_size=500, seq_len=32, global_batch=12)
    p = DataPipeline(cfg, 2, 1)
    q = p.reshard(3, 2)
    full = DataPipeline(cfg, 1, 0).batch(3)["tokens"]
    np.testing.assert_array_equal(
        np.asarray(q.batch(3)["tokens"]), np.asarray(full)[8:])


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------


def _state(seed=0):
    rng = np.random.default_rng(seed)
    return {"params": {"w": jnp.asarray(rng.normal(size=(8, 3)),
                                        jnp.float32)},
            "opt": {"mu": jnp.zeros((8, 3)), "count": jnp.int32(5)}}


def test_checkpoint_roundtrip_sharded(tmp_path):
    s = _state()
    save_checkpoint(tmp_path, 42, s, num_shards=3, meta={"next_step": 43})
    got, meta = restore_checkpoint(tmp_path, s)
    assert meta["next_step"] == 43
    np.testing.assert_array_equal(got["params"]["w"], s["params"]["w"])
    assert latest_step(tmp_path) == 42


def test_checkpoint_atomicity(tmp_path):
    s = _state()
    save_checkpoint(tmp_path, 1, s)
    # simulate a crash: partial dir without marker
    bad = tmp_path / "step_00000002"
    bad.mkdir()
    (bad / "manifest.json").write_text("{}")
    assert latest_step(tmp_path) == 1
    got, _ = restore_checkpoint(tmp_path, s)
    np.testing.assert_array_equal(got["opt"]["count"], 5)


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(tmp_path, num_shards=2)
    s = _state(1)
    ck.save(10, s)
    ck.wait()
    got, _ = restore_checkpoint(tmp_path, s)
    np.testing.assert_array_equal(got["params"]["w"], s["params"]["w"])


# ---------------------------------------------------------------------------
# runtime
# ---------------------------------------------------------------------------


def test_fault_tolerant_loop_resume(tmp_path):
    calls = []

    def step_fn(state, step):
        calls.append(step)
        return {"x": state["x"] + step}

    loop = FaultTolerantLoop(tmp_path, ckpt_every=4)
    s0 = {"x": jnp.float32(0)}
    state, stopped = loop.run(s0, step_fn, start_step=0, num_steps=10)
    assert stopped == 10
    # crash-restart: a fresh loop resumes from the last committed step
    loop2 = FaultTolerantLoop(tmp_path, ckpt_every=4)
    state2, start = loop2.resume_or_init(s0)
    assert start == 10
    assert float(state2["x"]) == float(state["x"]) == sum(range(10))


def test_preemption_checkpoints_and_stops(tmp_path):
    pre = PreemptionSignal()

    def step_fn(state, step):
        if step == 2:
            pre.trigger()
        return {"x": state["x"] + 1}

    loop = FaultTolerantLoop(tmp_path, ckpt_every=100, preemption=pre)
    state, stopped = loop.run({"x": jnp.float32(0)}, step_fn,
                              start_step=0, num_steps=50)
    assert stopped == 3            # stopped right after the signal
    st_, start = loop.resume_or_init({"x": jnp.float32(0)})
    assert start == 3 and float(st_["x"]) == 3


def test_straggler_rebalance():
    mon = StragglerMonitor(num_hosts=4, threshold=1.5)
    for t in range(8):
        for h in range(4):
            mon.record(h, 1.0 if h != 2 else 3.0)   # host 2 is slow
    assert mon.stragglers() == [2]
    asg = mon.rebalance()
    assert asg[2] == []
    assert sorted(sum(asg.values(), [])) == [0, 1, 2, 3]  # no shard lost


def test_remesh_plan():
    p = remesh_plan(global_batch=256, old_devices=512, new_devices=256,
                    data_axis_size=16)
    assert p.per_device_batch == 16
    with pytest.raises(ValueError):
        remesh_plan(global_batch=256, old_devices=512, new_devices=384,
                    data_axis_size=24)


# ---------------------------------------------------------------------------
# persistent compile cache (repro.compat.enable_compile_cache)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "fixed"])
def test_compile_cache_lands_in_one_directory(tmp_path, monkeypatch,
                                              env_dir):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the cache lands there and
    nowhere else; without it, at the fixed ``<checkout>/.jax_cache``."""
    from pathlib import Path

    from jax.experimental.compilation_cache import compilation_cache

    from repro import compat

    fixed = Path(compat.__file__).resolve().parents[2] / ".jax_cache"
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        compilation_cache.reset_cache()
        path = compat.enable_compile_cache()
        want = tmp_path if env_dir else fixed
        assert Path(path) == want
        assert Path(jax.config.jax_compilation_cache_dir) == want
        if env_dir:
            # cache even this tiny program, then look where it went
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
            seed = float(np.random.default_rng().integers(1 << 30))
            jax.jit(lambda x: x * seed + 1.0)(jnp.ones(3)).block_until_ready()
            assert any(tmp_path.iterdir()), "nothing cached in the env dir"
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
