"""CPU fixtures: four host devices, and a tiny copy of the benchmark.

Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

# before JAX starts: the four-chip cells' meshes on the CPU
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

TINY_MODEL = {
    "name": "tiny-llama", "source": "test", "model_type": "llama",
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "max_position_embeddings": 64, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "initializer_range": 0.02,
    "tie_word_embeddings": True, "param_dtype": "bfloat16",
    "compute_dtype": "bfloat16", "cache_dtype": "bfloat16",
    "reduced": []}
TINY_TRAIN = {
    "driver": "train", "global_batch": 8, "seq_len": 32, "mean_doc_len": 16,
    "bos_id": 1, "ring": 4, "check_steps": 3, "remat": True,
    "dp_mode": "explicit", "dp_algorithm": "auto",
    "dp_transport": "shardmap", "select_policy": "model",
    "optimizer": {"peak_lr": 3e-4, "warmup_steps": 0, "total_steps": 100,
                  "max_grad_norm": 1.0, "weight_decay": 0.1, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8}}
TINY_DECODE = {"driver": "decode", "batch": 4, "prompt_len": 16,
               "new_tokens": 8, "cache_len": 24, "mean_doc_len": 16,
               "bos_id": 1, "check_requests": 2}
# limits of the tiny cells, set as the real cells' are: above the
# largest reading of the program's sound runs on CPU (seeds 1-5: loss
# 2.0e-5, grad 6.3e-4, update 6.0e-4; decode 0) and below the fp8
# control's smallest (loss 1.1e-4, grad 5.7e-3, update 2.1e-3)
TINY_LIMITS = {
    "tiny.train.dp4": {"loss_gap": 6e-5, "grad_norm_gap": 2e-3,
                       "update_norm_gap": 1.2e-3},
    "tiny.train.1chip": {"loss_gap": 6e-5, "grad_norm_gap": 2e-3,
                         "update_norm_gap": 1.2e-3},
    "tiny.decode": {"served_logit_gap": 5e-3},
}


def make_tree(dst: Path) -> Path:
    """A checkout holding the benchmark with tiny cells added as new
    files and manifest entries, and the program beside it."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dst / "src").symlink_to(ROOT / "src")
    b = dst / "bench"
    (b / "configs" / "tiny-llama.json").write_text(json.dumps(TINY_MODEL))
    for name, w in (("tiny_train_dp4", dict(TINY_TRAIN, data_parallel=4)),
                    ("tiny_train_1chip", dict(TINY_TRAIN, data_parallel=1)),
                    ("tiny_decode", TINY_DECODE)):
        (b / "workloads" / f"{name}.json").write_text(json.dumps(w))
    for cell, lim in TINY_LIMITS.items():
        (b / "limits" / f"{cell}.json").write_text(
            json.dumps({"limits": lim}))
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-llama", "source": "test",
                         "file": "bench/configs/tiny-llama.json",
                         "reduced": [], "why": "test"})
    cells = [("tiny.train.dp4", "tiny-llama", "tiny_train_dp4", 4),
             ("tiny.train.1chip", "tiny-llama", "tiny_train_1chip", 1),
             ("tiny.decode", "tiny-llama", "tiny_decode", 1)]
    for name, cfg, traffic, chips in cells:
        m["workloads"].append({"name": name, "config": cfg,
                               "traffic": traffic, "chips": chips,
                               "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        ws = e.get("workloads")
        if ws is None:
            continue
        for name, _, traffic, _ in cells:
            if traffic.split("_")[1] in ws[0]:
                ws.append(name)
    (dst / "BENCHMARK.json").write_text(json.dumps(m, indent=1))
    return dst


@pytest.fixture(scope="session")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("checkout"))
