"""Sharding rules: FSDP + TP (+ EP) over the production meshes.

Axis convention (launch/mesh.py):
    single pod : ("data", "model")              = (16, 16)
    multi-pod  : ("pod", "data", "model")       = (2, 16, 16)

Rules (MaxText-style, by parameter role):
  * embedding [V, d]        -> (model, fsdp)       vocab-sharded
  * attn/mlp weights [.., a, b] -> contracting dim over fsdp, output dim
    over model (Megatron TP), stacked period dim replicated
  * MoE experts [.., E, a, b]  -> E over model (expert parallelism),
    a over fsdp
  * norms / biases / small vectors -> replicated
  * optimizer moments inherit their parameter's spec

``fsdp`` = ("pod", "data") on the multi-pod mesh, ("data",) on one pod:
parameter storage is fully sharded across every chip; the partitioner
inserts per-layer all-gathers (the xla-substrate path the MPIX layer
layers on).  Dims that don't divide fall back to replication (whisper's
odd 51865 vocab).
"""
from __future__ import annotations

import re

from jax.sharding import PartitionSpec as P

import jax
import numpy as np

_KEY_RE = re.compile(r"\['?([\w]+)'?\]")


def _leaf_name(path: str) -> str:
    """Last dict key in a tree_util keystr path."""
    keys = _KEY_RE.findall(path)
    return keys[-1] if keys else path


def data_axes(mesh) -> tuple[str, ...]:
    """Axes carrying the global batch (pod + data when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _fsdp_axes(mesh):
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _axis_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _divisible(dim: int, size: int) -> bool:
    return size > 0 and dim % size == 0


def param_specs(params, cfg, mesh):
    """Pytree of PartitionSpec matching ``params`` (dicts/lists of
    arrays)."""
    fsdp = _fsdp_axes(mesh)
    fsdp_n = _axis_size(mesh, fsdp)
    model_n = mesh.shape["model"]

    def spec_for(path: str, x) -> P:
        shape = x.shape
        nd = len(shape)
        name = _leaf_name(path)
        # norms, biases, scalars, small vectors -> replicated
        if nd <= 1 or x.size < 1 << 16:
            return P()
        # stacked-period leading axis is never sharded
        lead = 1 if "periods" in path else 0
        if name == "embed" or name == "lm_head":
            vdim, ddim = (0, 1) if name == "embed" else (1, 0)
            spec = [None] * nd
            if _divisible(shape[vdim], model_n):
                spec[vdim] = "model"
            if _divisible(shape[ddim], fsdp_n):
                spec[ddim] = fsdp
            return P(*spec)
        if nd - lead < 2:
            # stacked vector (periods norm scales etc.)
            return P()
        # expert-stacked weights: [.., E, a, b] with E the experts the
        # layer holds (n_experts unless it holds a share).
        # EP storage: experts over ("pod","model") when they divide (the
        # dispatch alltoall then crosses the DCN and the hierarchical
        # algorithm's locality aggregation applies), else "model".
        if cfg.moe is not None and nd - lead == 3 \
                and shape[lead] == cfg.moe.n_held:
            spec = [None] * nd
            ep = ("pod", "model") if "pod" in mesh.axis_names else ("model",)
            if not _divisible(shape[lead], _axis_size(mesh, ep)):
                ep = ("model",)
            if _divisible(shape[lead], _axis_size(mesh, ep)):
                spec[lead] = ep if len(ep) > 1 else "model"
            data_only = tuple(a for a in mesh.axis_names if a == "data")
            if _divisible(shape[lead + 1], _axis_size(mesh, data_only)):
                spec[lead + 1] = "data"
            return P(*spec)
        # generic matmul weight [.., a, b]: contracting over fsdp,
        # output over model (Megatron column parallel; works for row
        # parallel too since XLA re-shards as needed)
        spec = [None] * nd
        a_dim, b_dim = nd - 2, nd - 1
        if _divisible(shape[b_dim], model_n):
            spec[b_dim] = "model"
        if _divisible(shape[a_dim], fsdp_n):
            spec[a_dim] = fsdp
        return P(*spec)

    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    specs = [spec_for(jax.tree_util.keystr(kp), v) for kp, v in flat]
    return jax.tree_util.tree_unflatten(tdef, specs)


def batch_specs(mesh):
    """Token batches: rows over (pod, data); sequence replicated."""
    return P(data_axes(mesh))


def cache_specs(cache, cfg, mesh, *, long_context: bool):
    """KV caches: batch over the data axes and *sequence over model*
    (sequence-parallel KV — kv-head counts rarely divide the model axis,
    sequence always does; the partitioner turns the softmax over the
    sharded length into partial-softmax + psum).  Long-context (batch 1)
    shards the sequence over every axis."""
    d_axes = data_axes(mesh)
    all_axes = tuple(mesh.axis_names)

    def spec_for(path, x):
        nd = len(x.shape)
        name = _leaf_name(path)
        if nd == 0:
            return P()
        lead = 1 if "periods" in path else 0
        if name in ("k", "v"):        # [.., B, S, K, D]
            spec = [None] * nd
            if long_context:
                if _divisible(x.shape[lead + 1], _axis_size(mesh, all_axes)):
                    spec[lead + 1] = all_axes     # SP over every chip
                else:
                    spec[lead + 1] = d_axes
            else:
                spec[lead] = d_axes
                if _divisible(x.shape[lead + 1], mesh.shape["model"]):
                    spec[lead + 1] = "model"
            return P(*spec)
        if name in ("ckv", "kr"):     # MLA latent [.., B, S, r]
            spec = [None] * nd
            if long_context:
                spec[lead + 1] = d_axes
            else:
                spec[lead] = d_axes
                if _divisible(x.shape[lead + 1], mesh.shape["model"]):
                    spec[lead + 1] = "model"
            return P(*spec)
        if name == "s":               # rwkv state [.., B, H, N, N]
            spec = [None] * nd
            if _divisible(x.shape[lead + 1], mesh.shape["model"]):
                spec[lead + 1] = "model"
            if not long_context:
                spec[lead] = d_axes
            return P(*spec)
        if name == "h":               # mamba state [.., B, Di, S]
            spec = [None] * nd
            if _divisible(x.shape[lead + 1], mesh.shape["model"]):
                spec[lead + 1] = "model"
            if not long_context:
                spec[lead] = d_axes
            return P(*spec)
        if name == "conv":            # [.., B, K-1, Di]
            spec = [None] * nd
            if _divisible(x.shape[lead + 2], mesh.shape["model"]):
                spec[lead + 2] = "model"
            if not long_context:
                spec[lead] = d_axes
            return P(*spec)
        if name in ("x_tm", "x_cm"):  # [.., B, d]
            spec = [None] * nd
            if not long_context:
                spec[lead] = d_axes
            return P(*spec)
        return P()

    flat, tdef = jax.tree_util.tree_flatten_with_path(cache)
    specs = [spec_for(jax.tree_util.keystr(kp), v) for kp, v in flat]
    return jax.tree_util.tree_unflatten(tdef, specs)
