"""The Moonlight decode cell's pieces at a tiny size on the CPU: the
``decode_deepseek`` driver run through the harness's test path on a
tiny deepseek_v3 file, its control, and the cell's counts against hand
arithmetic."""
from __future__ import annotations

import json

import jax
import pytest

from bench import counts, counts_deepseek, harness
from bench.tests.conftest import ROOT

MOONLIGHT = json.loads(
    (ROOT / "bench/configs/moonlight-16b-a3b.json").read_text())
TINY_DS = dict(MOONLIGHT, name="tiny-moonlight", hidden_size=64,
               intermediate_size=96, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=4,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               kv_lora_rank=16, num_hidden_layers=3, vocab_size=512,
               num_experts_per_tok=3, n_routed_experts=2,
               expert_share={"router_experts": 8, "first_expert": 2,
                             "chips_per_layer": 4},
               initializer_range=0.1)
TINY_DS_DECODE = {"driver": "decode_deepseek", "batch": 4,
                  "prompt_len": 16, "new_tokens": 8, "cache_len": 24,
                  "mean_doc_len": 16, "bos_id": 1, "check_requests": 2}
# CPU readings (seeds 1-4), widest and mean gap: program 0.0 to 0.0122
# and 0.0 to 0.00104, fp8 control 0.139 to 0.278 and 0.0156 to 0.0282;
# each limit lies between them
LIMITS = {"served_logit_gap": 0.05, "served_logit_gap_mean": 0.005}
CELL = "tiny.ds.decode"


@pytest.fixture(scope="module")
def ds_tree(tree):
    b = tree / "bench"
    (b / "configs" / "tiny-moonlight.json").write_text(json.dumps(TINY_DS))
    (b / "workloads" / "tiny_ds_decode.json").write_text(
        json.dumps(TINY_DS_DECODE))
    (b / "limits" / f"{CELL}.json").write_text(
        json.dumps({"limits": LIMITS}))
    m = json.loads((tree / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-moonlight", "source": "test",
                         "file": "bench/configs/tiny-moonlight.json",
                         "reduced": ["n_routed_experts"], "why": "test"})
    m["workloads"].append({"name": CELL, "config": "tiny-moonlight",
                           "traffic": "tiny_ds_decode", "chips": 1,
                           "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "moonlight16b.decode.1chip" in e.get("workloads", []):
            e["workloads"].append(CELL)
    (tree / "BENCHMARK.json").write_text(json.dumps(m, indent=1))
    return tree


def test_the_harness_runs_the_deepseek_cell(ds_tree):
    out = harness.run(CELL, 2**33 + 5, 0.5, False, root=ds_tree,
                      on_accelerator=False, log=lambda s: 0)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"setup_s", "decode_tokens_per_s"}
    assert set(out["checks"]) == set(LIMITS)
    traced = harness.run(CELL, 3, 0.3, True, root=ds_tree,
                         on_accelerator=False, log=lambda s: 0)
    # no device trace on the CPU: the trace's readers report nothing
    assert traced["correct"] and traced["metrics"] == {}


@pytest.mark.parametrize("seed", [1, 2])
def test_deepseek_control_fails(ds_tree, seed):
    ctx = harness.cell_context(ds_tree, CELL, seed, jax.devices()[:1],
                               log=lambda s: 0)
    drv = harness.load_module(ds_tree / "bench" / "drivers"
                              / "decode_deepseek.py").Driver(ctx)
    drv.setup()
    drv.window(0.0, traced=False)
    seqs = drv.sample()
    prog, fp8 = drv.position_gaps(seqs), drv.position_gaps(seqs, True)
    assert drv.gaps(seqs) == prog.max()
    assert prog.max() <= LIMITS["served_logit_gap"]
    assert prog.mean() <= LIMITS["served_logit_gap_mean"]
    assert fp8.max() > LIMITS["served_logit_gap"] \
        or fp8.mean() > LIMITS["served_logit_gap_mean"]


def test_moonlight_counts():
    c = MOONLIGHT
    # attention per layer: q 2048*3072, kv_a 2048*576, kv_norm 512,
    # kv_b 512*4096, o 2048*2048, two norms 2*2048
    attn = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048 \
        + 2 * 2048
    routed = 26 * 8 * 3 * 2048 * 1408
    other = 27 * attn + 3 * 2048 * 11264 \
        + 26 * (2048 * 64 + 64 + 3 * 2048 * 2816) + 2048 + 2048 * 163840
    n = counts_deepseek.param_counts(c)
    assert n == {"embed": 163840 * 2048, "routed": routed, "other": other}
    # the program holds the same parameters (models/ counts them itself)
    assert counts_deepseek.held_param_count(c) == 3_364_615_296
    ctx = counts.mean_context(2048, 256)
    assert ctx == 2175.5
    lat = 27 * 32 * ctx * 576 * 2
    assert counts_deepseek.decode_bytes_per_step(c, 32, ctx) == \
        2 * 3_364_615_296 + lat
    assert counts_deepseek.decode_bytes_per_step(c, 32, ctx) == \
        pytest.approx(8.89e9, rel=1e-3)
    assert counts_deepseek.decode_flops_per_token(c, ctx) == \
        2 * (other + 6 / 64 * routed) \
        + 27 * (2 * 16 * ctx * 576 + 2 * 16 * ctx * 512)


def test_the_moe_scope_reader():
    from bench import scopes, trace
    mod = harness.load_module(ROOT / "bench" / "metrics" / "decode.moe_ms.py")

    def inp(summary):
        return harness.MetricInput(
            summary, harness.Window(1.0, 2, 0, {}, {"steps": 2}), {}, {}, 2)
    # 1 ms and 3 ms of busy time on two chips over 2 steps: 1 ms a step
    s = scopes.Summary(1e9, [0, 1], {0: 5e8, 1: 5e8},
                       {"decode.moe": {0: 1e6, 1: 3e6}}, {}, 0.0, [])
    assert mod.read(inp(s)) == pytest.approx(1.0)
    assert mod.read(inp(scopes.Summary(1e9, [0], {0: 5e8}, {}, {}, 1.0,
                                       []))) is None
    assert mod.read(inp(None)) is None
    assert mod.read(inp(trace.Summary(1e9, [0], {0: 5e8}, {0: 0.0}, {},
                                      []))) is None
