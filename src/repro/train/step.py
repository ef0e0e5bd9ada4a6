"""Train-step factory: wires the model zoo, the optimizer and the MPIX
communication layer into one jitted step per (arch, mesh, options).

Two DP modes (the paper's layering made operational):
  * ``fsdp``     — parameters FSDP-sharded (sharding.py), gradient
                   reduction left to the XLA partitioner: the "system
                   MPI" substrate.  Required for the 100B+ archs.
  * ``explicit`` — parameters replicated over the data axes; gradients
                   synchronized by *our* collectives inside shard_map
                   with a selectable algorithm + bucketing + optional
                   DCN int8 compression.  The paper-faithful path.

MoE modes: ``dropless`` (XLA-sharded gather dispatch) or ``mpix_ep``
(explicit expert-parallel alltoall through repro.core).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.optim import adamw_init, adamw_update, clip_by_global_norm
from repro.optim.schedule import cosine_schedule
from repro.train import sharding
from repro.train.moe_dispatch import EPOptions, make_moe_dispatch
from repro.train import sync


@dataclasses.dataclass(frozen=True)
class TrainOptions:
    dp_mode: str = "fsdp"              # "fsdp" | "explicit"
    dp_algorithm: str = "xla"          # explicit mode collective
    grad_buckets: int = 1
    compress_dcn: bool = False         # explicit+multi-pod only
    moe_mode: str = "dropless"         # "dense" | "dropless" | "mpix_ep"
    ep_alltoall: str = "xla"
    ep_capacity: float = 1.25
    ep_policy: str | None = None       # selection policy for EP "auto"
                                       # collectives (None = process
                                       # default set by the launcher)
    ep_overlap_chunks: int | None = None   # EPOptions.overlap_chunks:
                                       # pipelined MoE dispatch (None =
                                       # off, 0 = tuner-priced auto)
    ep_transport: str = "shardmap"     # EP collective substrate:
                                       # "shardmap" | "pallas" | "auto"
    dp_transport: str = "shardmap"     # explicit-mode grad-sync
                                       # substrate (same choices)
    overlap_grad_chunks: int = 0       # explicit mode: > 0 pipelines
                                       # grad sync as reduce-scatter /
                                       # clip-on-shards / allgather in
                                       # this many chunks (0 = off)
    resilience: object = None          # chaos-resilient collectives:
                                       # None/False off; True/"canary"/
                                       # "full"/dict arms the api
                                       # recovery ladder for EP dispatch
                                       # and explicit-mode grad sync
    remat: bool = True
    use_kernel: bool = False           # Pallas attention/wkv path
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    max_grad_norm: float = 1.0
    weight_decay: float = 0.1


def _loss_fn(cfg, opts: TrainOptions, moe_dispatch, reduction="mean"):
    def loss(params, batch):
        kw = {}
        if cfg.encoder is not None:
            kw["encoder_frames"] = batch["encoder_frames"]
        if cfg.vision_prefix:
            kw["vision_embeds"] = batch["vision_embeds"]
        with jax.named_scope("train.loss"):
            return M.lm_loss(params, cfg, batch["tokens"], batch["labels"],
                             use_kernel=opts.use_kernel, remat=opts.remat,
                             moe_dispatch=moe_dispatch, reduction=reduction,
                             **kw)
    return loss


def init_train_state(key, cfg, opts: TrainOptions | None = None):
    params = M.init_params(key, cfg)
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    if opts is not None and opts.compress_dcn:
        state["ef_residual"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return state


def state_specs(state, cfg, mesh, opts: TrainOptions):
    """PartitionSpec tree for the train state under the chosen mode."""
    if opts.dp_mode == "explicit":
        return jax.tree.map(lambda _: P(), state)
    pspecs = sharding.param_specs(state["params"], cfg, mesh)
    out = {"params": pspecs,
           "opt": {"mu": pspecs, "nu": pspecs, "count": P()},
           "step": P()}
    if "ef_residual" in state:
        out["ef_residual"] = pspecs
    return out


def make_train_step(cfg, mesh, opts: TrainOptions) -> Callable:
    """Returns ``train_step(state, batch) -> (state, metrics)``, to be
    jitted.  Its device ops carry the named scopes ``train.loss``
    (forward ``jvp(train.loss)``, backward ``transpose(jvp(train.loss))``),
    ``train.count_psum``, ``train.grad_sync`` and ``train.optimizer``."""
    moe_dispatch = None
    if opts.moe_mode == "mpix_ep" and cfg.moe is not None:
        moe_dispatch = make_moe_dispatch(
            mesh, EPOptions(alltoall=opts.ep_alltoall,
                            capacity_factor=opts.ep_capacity,
                            policy=opts.ep_policy,
                            overlap_chunks=opts.ep_overlap_chunks,
                            transport=opts.ep_transport,
                            resilience=opts.resilience),
            cfg.mlp_act)
    elif opts.moe_mode == "dropless" and cfg.moe is not None:
        moe_dispatch = lambda p, c, x: moe_mod.forward_dropless(
            p, c, x, cfg.mlp_act)
    loss = _loss_fn(cfg, opts, moe_dispatch)

    @jax.named_scope("train.optimizer")
    def opt_apply(state, grads, gnorm=None):
        lr = cosine_schedule(state["step"], peak_lr=opts.peak_lr,
                             warmup_steps=opts.warmup_steps,
                             total_steps=opts.total_steps)
        if gnorm is None:
            grads, gnorm = clip_by_global_norm(grads, opts.max_grad_norm)
        params, opt = adamw_update(state["params"], grads, state["opt"],
                                   lr=lr, weight_decay=opts.weight_decay)
        return params, opt, gnorm, lr

    d_axes = sharding.data_axes(mesh)

    if opts.dp_mode == "fsdp":
        def train_step(state, batch):
            lval, grads = jax.value_and_grad(loss)(state["params"], batch)
            params, opt, gnorm, lr = opt_apply(state, grads)
            new = dict(state, params=params, opt=opt,
                       step=state["step"] + 1)
            return new, {"loss": lval, "grad_norm": gnorm, "lr": lr}
        return train_step

    # ---- explicit mode: replicated params, manual DP sync --------------
    # Per-shard losses are SUMS over live tokens; shards exchange
    # (grad-sum, token-count) so the combined update equals the exact
    # global-mean gradient even under uneven label masking.
    sum_loss = _loss_fn(cfg, opts, moe_dispatch, reduction="sum_count")

    # pipelined grad sync (reduce-scatter / clip-on-shards / allgather):
    # the clip norm is computed on the scattered shards so the optimizer
    # prologue overlaps the allgather.  Compression owns the DCN hop, so
    # the two paths are mutually exclusive.
    overlap = (opts.overlap_grad_chunks > 0
               and not (opts.compress_dcn and "pod" in mesh.axis_names))

    def train_step(state, batch):
        def body(params, residual, batch):
            def local(p):
                s, c = sum_loss(p, batch)
                return s, c
            (lsum, cnt), grads = jax.value_and_grad(
                local, has_aux=True)(params)
            with jax.named_scope("train.count_psum"):
                cnt_g = jax.lax.psum(cnt, d_axes)
            denom = jnp.maximum(cnt_g, 1).astype(jnp.float32)
            gnorm = None
            with jax.named_scope("train.grad_sync"):
                if opts.compress_dcn and "pod" in mesh.axis_names:
                    grads, residual = sync.dp_allreduce_compressed(
                        grads, residual, intra_algorithm=opts.dp_algorithm,
                        denom=denom, resilience=opts.resilience)
                elif overlap:
                    grads, gnorm = sync.dp_allreduce_overlap(
                        grads, d_axes, algorithm=opts.dp_algorithm,
                        chunks=opts.overlap_grad_chunks, denom=denom,
                        max_norm=opts.max_grad_norm,
                        transport=opts.dp_transport,
                        resilience=opts.resilience)
                else:
                    grads = sync.dp_allreduce(
                        grads, d_axes, algorithm=opts.dp_algorithm,
                        buckets=opts.grad_buckets, denom=denom,
                        transport=opts.dp_transport,
                        resilience=opts.resilience)
            with jax.named_scope("train.count_psum"):
                lval = jax.lax.psum(lsum, d_axes) / denom
            return lval, grads, residual, gnorm

        residual = state.get("ef_residual")
        shard = compat.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), state["params"]),
                      (jax.tree.map(lambda _: P(), residual)
                       if residual is not None else None),
                      jax.tree.map(lambda _: P(d_axes), batch)),
            out_specs=(P(),
                       jax.tree.map(lambda _: P(), state["params"]),
                       (jax.tree.map(lambda _: P(), residual)
                        if residual is not None else None),
                       P() if overlap else None),
            check_vma=False)
        lval, grads, residual, gnorm = shard(state["params"], residual,
                                             batch)
        params, opt, gnorm, lr = opt_apply(state, grads, gnorm=gnorm)
        new = dict(state, params=params, opt=opt, step=state["step"] + 1)
        if residual is not None:
            new["ef_residual"] = residual
        return new, {"loss": lval, "grad_norm": gnorm, "lr": lr}

    return train_step


def jit_train_step(cfg, mesh, opts: TrainOptions, state, batch_spec_tree):
    """jit with explicit in/out shardings for the dry-run and launchers."""
    step = make_train_step(cfg, mesh, opts)
    sspec = state_specs(state, cfg, mesh, opts)
    to_sh = lambda spec: jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec,
        is_leaf=lambda x: isinstance(x, P))
    return jax.jit(step,
                   in_shardings=(to_sh(sspec), to_sh(batch_spec_tree)),
                   out_shardings=(to_sh(sspec), None)), sspec
