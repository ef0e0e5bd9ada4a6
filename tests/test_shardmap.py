"""Multi-device shard_map tests, run in subprocesses (jax locks the host
device count at first init, and the main pytest process must keep seeing
exactly 1 device for the smoke tests)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPTS = Path(__file__).parent / "device_scripts"
SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_script(name: str, *args: str, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, str(SCRIPTS / name), *args],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, (
        f"--- stdout ---\n{proc.stdout[-4000:]}\n"
        f"--- stderr ---\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.mark.slow
def test_shardmap_transport_all_collectives():
    out = run_script("check_shardmap_transport.py")
    assert "ALL OK" in out


@pytest.mark.slow
def test_unified_ir_transports_bit_exact():
    """SimTransport == ShardMapTransport on the unified IR for every
    registered schedule x {flat, 2-pod, 2x4 torus, 3-level} x
    {f32, bf16} (the deeper 2x(4x2) sweep runs from
    test_hierarchical.py via check_hierarchical.py)."""
    out = run_script("check_unified_ir.py")
    assert "ALL OK" in out


@pytest.mark.slow
def test_executor_cache_trace_counts_and_fusion():
    """Persistent-executor proof: one jit trace per (schedule, shape,
    dtype) across repeated calls, api-path cache sharing, fused-vs-
    reference bit-exactness where fusion cuts rounds, and cache
    invalidation on env-flag / fingerprint changes."""
    out = run_script("check_executor.py")
    assert "ALL OK" in out


@pytest.mark.slow
def test_pallas_transport_device_paths():
    """Device-side single-kernel transport (PallasTransport) inside
    real shard_map: bit-exact vs ShardMapTransport for every dense
    collective + neighbor plan + overlap path, and the fused
    allreduce->rmsnorm epilogue vs its unfused oracle."""
    out = run_script("check_pallas_transport.py")
    assert "ALL OK" in out


@pytest.mark.slow
def test_neighbor_plan_shardmap():
    out = run_script("check_neighbor_shardmap.py")
    assert "ALL OK" in out


@pytest.mark.slow
def test_partitioned_and_pipeline():
    out = run_script("check_partitioned.py")
    assert "ALL OK" in out


@pytest.mark.slow
def test_distributed_train_paths():
    out = run_script("check_train_dist.py")
    assert "ALL OK" in out


@pytest.mark.slow
def test_pipelined_overlap_paths():
    """Compute-comm overlap hot paths (PR 6): chunked shard_map
    transport, mpix_alltoall_overlap, MoE dispatch overlap, grad-sync
    overlap in the explicit train step, and the serve prefill EP
    wiring — all equivalent to their unpipelined oracles."""
    out = run_script("check_overlap.py")
    assert "ALL OK" in out


@pytest.mark.slow
def test_multi_pod_dryrun_cells():
    out = run_script("check_dryrun_cell.py")
    assert "ALL OK" in out


@pytest.mark.slow
def test_elastic_remesh_restore():
    out = run_script("check_elastic.py")
    assert "ALL OK" in out


@pytest.mark.slow
def test_serve_device_paths():
    """Continuous-batching serving on real shards: jit_decode_step's
    cache NamedShardings actually land (the bare-jax.jit launcher bug),
    KV-transfer plans bit-exact on shardmap + pallas transports, and a
    full engine trace drained with transport="shardmap"."""
    out = run_script("check_serve.py")
    assert "ALL OK" in out
