"""The yardstick's counts against hand arithmetic, and the peaks table."""
from __future__ import annotations

import json

import pytest

from bench import counts, peaks
from bench.tests.conftest import ROOT

SMOLLM = json.loads((ROOT / "bench/configs/smollm-360m.json").read_text())


def test_smollm_360m_parameters():
    # embed 49152*960; per layer 2 norms, q 960*960, k and v 960*320,
    # o 960*960, gate/up/down 3*960*2560; final norm 960; tied head
    per_layer = 2 * 960 + 960 * 960 + 2 * 960 * 320 + 960 * 960 \
        + 3 * 960 * 2560
    assert per_layer == 9_832_320
    assert counts.dense_param_count(SMOLLM) == \
        49152 * 960 + 32 * per_layer + 960 == 361_821_120


def test_train_flops_per_token_is_2_36_gflop():
    f = counts.train_flops_per_token(SMOLLM, 1024)
    assert f == 6 * 361_821_120 + 6 * 32 * 1024 * 960
    assert f == pytest.approx(2.36e9, rel=2e-3)
    # one 8 x 1024 step: about 19.3 TFLOP
    assert f * 8 * 1024 == pytest.approx(19.3e12, rel=3e-3)


def test_decode_counts():
    ctx = counts.mean_context(1024, 256)
    assert ctx == 1151.5          # attends over 1024 ... 1279 positions
    assert counts.decode_flops_per_token(SMOLLM, ctx) == \
        2 * 361_821_120 + 4 * 32 * 1151.5 * 960
    kv = 2 * 32 * 32 * 1151.5 * 5 * 64 * 2        # k and v, bf16
    assert counts.decode_bytes_per_step(SMOLLM, 32, ctx) == \
        2 * 361_821_120 + kv
    assert kv == pytest.approx(1.509e9, rel=1e-3)


def test_peaks_of_an_unknown_device_are_an_error():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_shares_are_taken_over_the_device_time_of_the_window():
    from bench import harness
    from bench.trace import Summary
    # 10 steps of 10 tokens in a 2 s window; the device ran for 1 s
    s = Summary(2e9, [0], {0: 1e9}, {0: 0.0}, {}, [])
    win = harness.Window(2.0, 10, 0, {}, {"steps": 10, "tokens": 100})
    pk = peaks.peaks("TPU v5 lite")
    m = harness.MetricInput(s, win, {"flops_per_token": 1e11,
                                     "bytes_per_step": 1e10}, pk, 1)

    def read(name, inp=m):
        return harness.load_module(
            ROOT / "bench" / "metrics" / f"{name}.py").read(inp)
    assert read("train.mfu") == pytest.approx(100 * 1e13 / 197e12)
    assert read("decode.mfu") == pytest.approx(100 * 1e13 / 197e12)
    assert read("decode.hbm_share") == pytest.approx(100 * 1e11 / 819e9)
    assert read("device.idle_share.train") == pytest.approx(50.0)
    untraced = harness.MetricInput(None, win, m.counts, pk, 1)
    assert read("train.mfu", untraced) is None
