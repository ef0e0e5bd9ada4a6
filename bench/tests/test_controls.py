"""The controls come out not correct at a size a test run can hold: the
reference put in the program's place one precision step below what the
configuration states (fp8 for the bfloat16 model) fails at least one of
the cell's numbers, where the program's sound run passes them all."""
from __future__ import annotations

import json

import jax
import pytest

from bench import harness
from bench.drivers.train import numbers
from bench.tests import conftest


def driver(tree, cell, seed):
    ctx = harness.cell_context(tree, cell, seed, jax.devices()[:1],
                               log=lambda s: 0)
    drv = harness.load_module(tree / "bench" / "drivers"
                              / f"{ctx.workload['driver']}.py").Driver(ctx)
    drv.setup()
    return drv


def fails(got: dict, limits: dict) -> bool:
    return any(v > limits[k] for k, v in got.items())


@pytest.mark.parametrize("seed", [1, 2])
def test_train_control_fails(tree, seed):
    drv = driver(tree, "tiny.train.1chip", seed)
    lim = conftest.TINY_LIMITS["tiny.train.1chip"]
    prog = (drv.losses, drv.grad, drv.change)
    ref = drv.reference()
    assert not fails(numbers(prog, ref), lim)
    assert fails(numbers(drv.reference(fp8=True), ref), lim)


@pytest.fixture(scope="module")
def small_decode(tree):
    """A decode cell wide enough that fp8 moves the greedy tokens."""
    small = dict(conftest.TINY_MODEL, name="small-llama", hidden_size=256,
                 intermediate_size=512, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=64, vocab_size=4096)
    b = tree / "bench"
    (b / "configs" / "small-llama.json").write_text(json.dumps(small))
    (b / "workloads" / "small_decode.json").write_text(json.dumps(dict(
        conftest.TINY_DECODE, prompt_len=32, new_tokens=32, cache_len=64,
        check_requests=4)))
    (b / "limits" / "small.decode.json").write_text(json.dumps(
        {"limits": {"served_logit_gap": 5e-3}}))
    m = json.loads((tree / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "small-llama", "source": "test",
                         "file": "bench/configs/small-llama.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "small.decode", "config": "small-llama",
                           "traffic": "small_decode", "chips": 1,
                           "why": "test"})
    (tree / "BENCHMARK.json").write_text(json.dumps(m))
    return "small.decode"


# CPU readings (seeds 1, 2): program 0.0020 and 0.0020, control 0.010
# and 0.021; the limit 5e-3 lies between them
@pytest.mark.parametrize("seed", [1, 2])
def test_decode_control_fails(tree, small_decode, seed):
    drv = driver(tree, small_decode, seed)
    drv.window(0.0, traced=False)
    seqs = drv.sample()
    lim = drv.ctx.limits["limits"]["served_logit_gap"]
    assert drv.gaps(seqs) <= lim
    assert drv.gaps(seqs, fp8_pick=True) > lim
