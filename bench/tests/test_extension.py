"""A new configuration, traffic mix and per-layer metric are new files
plus new BENCHMARK.json entries: the harness finds them by name, with
no edit to any file that exists.  Runs at a tiny size on the CPU through
the harness's test path; ``bench/run.py`` itself refuses the CPU."""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.conftest import ROOT

METRIC = '''"""Steps per second of the window (a throwaway metric)."""


def read(m):
    return m.window.facts["steps"] / m.window.seconds
'''


def digest(root):
    return {p.relative_to(root).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest()
        for p in sorted((root / "bench").rglob("*"))
        if p.is_file() and "__pycache__" not in p.parts
        and "tests" not in p.parts}


@pytest.fixture(scope="module")
def extended(tree):
    """The tiny tree, plus one more metric registered for a tiny cell."""
    (tree / "bench" / "metrics" / "test.steps_per_s.py").write_text(METRIC)
    m = json.loads((tree / "BENCHMARK.json").read_text())
    m["per_layer"].append({
        "name": "test.steps_per_s", "unit": "steps/s", "better": "higher",
        "source": "host_clock", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": ["tiny.train.1chip"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(m))
    return tree


def test_new_files_leave_every_existing_file_as_it_was(extended):
    before, after = digest(ROOT), digest(extended)
    assert all(after[k] == v for k, v in before.items())
    added = set(after) - set(before)
    assert {"bench/configs/tiny-llama.json",
            "bench/workloads/tiny_train_1chip.json",
            "bench/limits/tiny.train.1chip.json",
            "bench/metrics/test.steps_per_s.py"} <= added


def test_the_harness_runs_the_new_cell_and_metric(extended):
    out = harness.run("tiny.train.1chip", 2**33 + 17, 0.5, False,
                      root=extended, on_accelerator=False, log=lambda s: 0)
    assert out["correct"] and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert list(out)[-1] == "checks"
    traced = harness.run("tiny.train.1chip", 5, 0.5, True, root=extended,
                         on_accelerator=False, log=lambda s: 0)
    assert traced["metrics"]["test.steps_per_s"]["value"] > 0
    assert traced["metrics"]["test.steps_per_s"]["unit"] == "steps/s"


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm360m.train.1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_py_refuses_the_cpu():
    r = _run_py(ROOT)
    assert r.returncode == 3, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "no result" in r.stderr


def test_run_py_fails_without_the_program(tmp_path):
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    r = _run_py(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_a_number_listed_as_not_compared_is_logged_not_checked(tree):
    from bench.tests.conftest import TINY_LIMITS
    lim = dict(TINY_LIMITS["tiny.train.1chip"])
    del lim["grad_norm_gap"]
    (tree / "bench" / "limits" / "tiny.train.partial.json").write_text(
        json.dumps({"limits": lim, "not_compared": ["grad_norm_gap"]}))
    m = json.loads((tree / "BENCHMARK.json").read_text())
    m["workloads"].append({"name": "tiny.train.partial",
                           "config": "tiny-llama",
                           "traffic": "tiny_train_1chip", "chips": 1,
                           "why": "test"})
    (tree / "BENCHMARK.json").write_text(json.dumps(m))
    lines = []
    out = harness.run("tiny.train.partial", 4, 0.3, False, root=tree,
                      on_accelerator=False, log=lines.append)
    assert out["correct"]
    assert set(out["checks"]) == {"loss_gap", "update_norm_gap"}
    assert any(s.startswith("reading grad_norm_gap ") for s in lines)
