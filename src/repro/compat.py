"""JAX calls with the repo's shared defaults, and its compile cache.

The repo is written against the installed JAX (``pyproject.toml`` pins
it) and calls it directly; the two wrappers here only fix arguments
every call site shares: ``shard_map`` with ``check_vma`` off by default,
``make_mesh`` with Auto axis types.

``enable_compile_cache()`` puts JAX's persistent compilation cache at a
fixed path; the launchers and ``chip_smoke.py`` call it from ``main()``,
never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — fixed, so a later process finds what an
# earlier one compiled (the path is part of the cache key)
_REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis_types."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself) and no other directory is set.  Otherwise the cache lives at
    the fixed ``<checkout>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_REPO_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
