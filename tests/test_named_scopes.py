"""The named scopes a profile of the train and decode steps is read by.

Each step is lowered (not run) and the scopes are looked for in the HLO
``op_name`` metadata, where the profiler's op events take their path
from.  The explicit-mode train step is lowered on a 4-way and a 1-way
abstract data mesh, so no device is needed for the collective path."""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, AxisType

from repro import configs
from repro.serve.step import ServeOptions, init_serve_cache, make_decode_step
from repro.train.step import TrainOptions, init_train_state, make_train_step
from repro.models import model as M

FORWARD = re.compile(r"(?<!transpose\()jvp\(train\.loss\)")


def op_names(lowered) -> list[str]:
    """Every ``op_name`` in the lowered program's HLO metadata."""
    opts = jax._src.lib._jax.HloPrintOptions.short_parsable()
    opts.print_metadata = True
    text = lowered.compiler_ir("hlo").get_hlo_module().to_string(opts)
    return re.findall(r'op_name="([^"]*)"', text)


def has(names, scope: str) -> bool:
    return any(scope in n.split("/") for n in names)


@pytest.mark.parametrize("data", [4, 1])
def test_explicit_train_step_scopes(data):
    cfg = configs.get_smoke("smollm-360m")
    mesh = AbstractMesh((data, 1), ("data", "model"),
                        axis_types=(AxisType.Auto, AxisType.Auto))
    opts = TrainOptions(dp_mode="explicit",
                        dp_algorithm="recursive_halving_doubling")
    state = jax.eval_shape(
        lambda: init_train_state(jax.random.key(0), cfg, opts))
    batch = {k: jax.ShapeDtypeStruct((8, 16), jnp.int32)
             for k in ("tokens", "labels")}
    step = make_train_step(cfg, mesh, opts)
    assert step.__name__ == "train_step"
    with jax.sharding.use_abstract_mesh(mesh):
        names = op_names(jax.jit(step).lower(state, batch))
    assert any(FORWARD.search(n) for n in names)
    for scope in ("transpose(jvp(train.loss))", "train.count_psum",
                  "train.grad_sync", "train.optimizer"):
        assert has(names, scope), scope
    mpix = {c for n in names for c in n.split("/")
            if c.startswith("mpix.allreduce.")}
    want = {"mpix.allreduce.recursive_halving_doubling.shardmap"}
    # on one rank the schedule has no round, and so no op
    assert mpix == want if data > 1 else mpix <= want
    # the collective runs inside the grad sync, not beside it
    assert all("train.grad_sync/mpix." in n for n in names
               if "mpix." in n)


def test_native_allreduce_scope_names_xla():
    cfg = configs.get_smoke("smollm-360m")
    mesh = AbstractMesh((2, 1), ("data", "model"),
                        axis_types=(AxisType.Auto, AxisType.Auto))
    opts = TrainOptions(dp_mode="explicit", dp_algorithm="xla")
    state = jax.eval_shape(
        lambda: init_train_state(jax.random.key(0), cfg, opts))
    batch = {k: jax.ShapeDtypeStruct((4, 16), jnp.int32)
             for k in ("tokens", "labels")}
    with jax.sharding.use_abstract_mesh(mesh):
        names = op_names(jax.jit(make_train_step(cfg, mesh, opts))
                         .lower(state, batch))
    assert has(names, "mpix.allreduce.xla")


def test_decode_step_scopes():
    cfg = configs.get_smoke("smollm-360m")
    params = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: init_serve_cache(cfg, 2, 16))
    step = make_decode_step(cfg, None, ServeOptions())
    assert step.__name__ == "decode_step"
    tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    names = op_names(jax.jit(step).lower(params, cache, tokens))
    assert has(names, "decode.attention")
    writes = [n for n in names if "decode.cache_write" in n.split("/")]
    assert sum(n.endswith("/dynamic_update_slice") for n in writes) == 2


def test_moonlight_decode_step_scopes():
    """Moonlight's decode step: latent attention, its two latent writes
    (in the dense prefix block and in the scanned MoE block) and the MoE
    layer (router, held and shared experts) each under its scope."""
    cfg = configs.get_smoke("moonshot-v1-16b-a3b")
    params = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: init_serve_cache(cfg, 2, 16))
    step = make_decode_step(cfg, None, ServeOptions())
    tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    names = op_names(jax.jit(step).lower(params, cache, tokens))
    for scope in ("decode.attention", "decode.cache_write", "decode.moe"):
        assert has(names, scope), scope
    writes = [n for n in names if "decode.cache_write" in n.split("/")]
    assert sum(n.endswith("/dynamic_update_slice") for n in writes) == 4
    moe = [n for n in names if "decode.moe" in n.split("/")]
    assert any("top_k" in n for n in moe)
    assert not any("decode.attention" in n.split("/") for n in moe)
