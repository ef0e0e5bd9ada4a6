"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each case lowers one kernel at real width for one chip of
a described ``v5e:2x2`` topology (no chip attached) and asserts that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).  This is
what the interpreter cannot show: tiling rules, scoped VMEM, and
primitives Mosaic cannot lower.  The topology is described inside a
module-scoped fixture, so collection never touches the TPU library and
the tests skip where it cannot be described.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.algorithms import REGISTRY
from repro.core.executor import get_executor
from repro.core.pallas_lowering import PallasExec
from repro.core.topology import flat_topology
from repro.kernels.attention.kernel import flash_attention_bhsd
from repro.kernels.rmsnorm.kernel import rmsnorm_2d, rmsnorm_reduce_2d

SMOLLM = get_config("smollm-360m")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


# ---------------------------------------------------------------------------
# the Pallas transport kernel: one schedule, one kernel, n=4
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("gbytes", [8 << 10, 2 << 20], ids=["8KiB", "2MiB"])
@pytest.mark.parametrize("name", ["allgather.ring", "allreduce.ring_rs_ag",
                                  "alltoall.pairwise"])
def test_pallas_transport_kernel_compiles(one_chip, name, gbytes):
    coll, algo = name.split(".")
    topo = flat_topology(4)
    sched = REGISTRY[coll][algo](topo)
    pex = PallasExec(get_executor(sched, topo=topo), interpret=False)
    shape = (4, sched.num_slots, gbytes // (4 * sched.num_slots * 4))
    lowered = pex.lower(shape, jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in lowered.compile().as_text()


# ---------------------------------------------------------------------------
# compute kernels at smollm-360m width
# ---------------------------------------------------------------------------


def test_rmsnorm_compiles_at_smollm_width(one_chip):
    d = SMOLLM.d_model
    x = jax.ShapeDtypeStruct((16384, d), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(
        lambda x, s: rmsnorm_2d(x, s, interpret=False), x, s)


def test_rmsnorm_allreduce_compiles_at_smollm_width(one_chip):
    d = SMOLLM.d_model
    p = jax.ShapeDtypeStruct((4, 16384, d), jnp.bfloat16,
                             sharding=one_chip)
    s = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(
        lambda p, s: rmsnorm_reduce_2d(p, s, interpret=False), p, s)


@pytest.mark.parametrize("gather", [False, True], ids=["plain", "q_rows"])
def test_flash_attention_compiles_at_smollm_width(one_chip, gather):
    a = SMOLLM.attn
    B, S = 4, 2048
    q = jax.ShapeDtypeStruct((B * a.n_heads, S, a.head_dim), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B * a.n_kv_heads, S, a.head_dim),
                              jnp.bfloat16, sharding=one_chip)
    if not gather:
        fn = lambda q, k, v: flash_attention_bhsd(q, k, v, interpret=False)
        text = _compiled_text(fn, q, kv, kv)
    else:
        rows = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one_chip)
        fn = lambda q, k, v, r: flash_attention_bhsd(
            q, k, v, q_rows=r, nheads=a.n_heads, interpret=False)
        text = _compiled_text(fn, q, kv, kv, rows)
    assert "tpu_custom_call" in text


def test_flash_attention_q_rows_compiles_at_f32(one_chip):
    """The f32 gather path asks Mosaic for a HIGHEST-precision one-hot
    matmul (the exact-copy guarantee)."""
    q = jax.ShapeDtypeStruct((8, 256, 64), jnp.float32, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((2, 256), jnp.int32, sharding=one_chip)
    fn = lambda q, k, v, r: flash_attention_bhsd(
        q, k, v, q_rows=r, nheads=4, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, q, q, q, rows)
