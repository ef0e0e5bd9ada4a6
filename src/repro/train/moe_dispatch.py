"""Expert-parallel MoE dispatch through the MPIX layer (paper §2.1+§2.2).

Experts are sharded over the EP axes (("pod","model") when the expert
count divides, else ("model",)); tokens travel to their experts through
``mpix_alltoall`` with a *selectable algorithm* — on the multi-pod mesh
the ``hierarchical`` algorithm aggregates everything headed to a remote
pod inside the source pod first (one DCN bundle per pod-pair stripe),
which is exactly the paper's locality-aware optimization applied to MoE
traffic.

Layout contract inside the shard_map:
  x        [B_local, S, d]   batch sharded over (pod, data); replicated
                             over model — each model rank takes its
                             1/M slice of the tokens.
  experts  [E_local, d, f]   E sharded over the EP axes.
  router   [d, E]            replicated.

Dispatch is capacity-based (static shapes; overflow drops, standard for
TPU MoE): per-source capacity C = ceil(T_slice * k / E * factor).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import api as mpix
from repro.core.transport import _flat_rank
from repro.models import mlp, moe
from repro.models.config import MoEConfig

from repro import compat


@dataclasses.dataclass(frozen=True)
class EPOptions:
    alltoall: str = "xla"           # mpix algorithm for dispatch/return
    allgather: str = "xla"          # rebuild of the token slice
    capacity_factor: float = 1.25
    policy: str | None = None       # selection policy for "auto" algos
                                    # (None = process default; "tuned"
                                    # reads tuner.autotune's table)
    overlap_chunks: int | None = None
    # pipelined dispatch (MPIPCL partitioned comm): the dispatch
    # alltoall runs in capacity chunks, each chunk's expert MLP
    # overlapping the next chunk's transfer.  None = off (monolithic),
    # 0 = auto (tuner prices the software pipeline against the expert
    # FLOPs per chunk), >= 2 = explicit chunk count (clamped to the
    # largest divisor of the capacity C).  Bit-exact either way.
    transport: str = "shardmap"
    # substrate for the schedule-backed collectives: "shardmap" (one
    # ppermute per compiled round), "pallas" (the whole schedule as one
    # device-side kernel — core.pallas_lowering), or "auto" (tuner's
    # per-size-bucket choice).  Ignored by "xla" algorithms.
    resilience: object = None
    # chaos-resilient execution for the dispatch collectives: None/False
    # = off, True/"canary"/"full"/dict/ResilienceOptions arm the api
    # recovery ladder (retry + transport fallback + algorithm refit +
    # xla) — see core.resilient.resolve_resilience.


def ep_axes_for(cfg_moe: MoEConfig, mesh) -> tuple[str, ...]:
    names = mesh.axis_names
    if "pod" in names:
        n = mesh.shape["pod"] * mesh.shape["model"]
        if cfg_moe.n_experts % n == 0:
            return ("pod", "model")
    return ("model",)


def make_moe_dispatch(mesh, opts: EPOptions, act: str = "silu"):
    """Returns a callable (p, cfg, x) -> y pluggable into model.forward.

    Must be called from inside the auto-sharded jit: drops into a
    shard_map over the mesh for the dispatch, computes shared experts in
    the auto region.
    """

    def dispatch(p, cfg: MoEConfig, x):
        ep = ep_axes_for(cfg, mesh)
        # batch rows stay sharded over every data-carrying axis; when
        # "pod" is also an EP axis the pod boundary separates *sources*
        # inside one EP group (each source dispatches its own tokens)
        d_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        xs_spec = P(d_axes)                          # batch dim sharding

        rp = {k: p[k] for k in ("router", "router_bias") if k in p}
        body = functools.partial(_dispatch_body, cfg=cfg, ep=ep,
                                 opts=opts, act=act)
        shard = compat.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), rp),   # router params
                      P(ep, None, None),         # w_gate  [E, d, f]
                      P(ep, None, None),         # w_up
                      P(ep, None, None),         # w_down  [E, f, d]
                      xs_spec),                  # x [B, S, d]
            out_specs=xs_spec, check_vma=False)
        out = shard(rp, p["w_gate"], p["w_up"], p["w_down"], x)
        if cfg.n_shared:
            out = out + mlp.forward(p["shared"], x, act)
        return out

    return dispatch


def _overlap_chunks(opts: EPOptions, *, cfg: MoEConfig, ep, E_loc: int,
                    N_ep: int, C: int, d: int, f: int,
                    itemsize: int) -> int:
    """Resolve ``EPOptions.overlap_chunks`` to an effective chunk count
    (a divisor of the capacity C; < 2 means run the monolithic path)."""
    ov = opts.overlap_chunks
    if ov is None:
        return 1
    if ov < 0:
        raise ValueError(
            f"EPOptions.overlap_chunks must be None (off), 0 (auto) or "
            f">= 1, got {ov}")
    if ov == 0:
        from repro.core import tuner
        from repro.core.topology import PEAK_FLOPS_BF16
        # 3 einsums x 2*rows*d*f flops over the full dispatch
        compute_s = (6.0 * E_loc * (N_ep * C) * d * f
                     / PEAK_FLOPS_BF16)
        topo = mpix.topology_from_axes(ep)
        ov = tuner.select_overlap_chunks(
            topo, cfg.n_experts * C * d * itemsize, compute_s,
            policy=opts.policy or mpix.get_default_policy())
    ov = min(ov, C)
    while ov > 1 and C % ov:
        ov -= 1
    return ov


def _dispatch_overlapped(send, w_gate, w_up, w_down, *, chunks: int,
                         ep, opts: EPOptions, act, N_ep: int,
                         E_loc: int, C: int, d: int):
    """Pipelined dispatch: the alltoall ships capacity chunks and each
    arriving chunk immediately feeds the expert MLPs while the next
    chunk is in flight (receive-side early-bird, MPIPCL §2.3).

    The send buffer is reordered capacity-major within each destination
    block so a row chunk is capacity slice ``i`` of EVERY local expert
    — a full-width einsum's worth of work per chunk.  Chunk results
    accumulate into the same [E_loc, N_ep, C, d] layout the monolithic
    path produces; per-row MLPs contract only over ``d``, so chunking
    is exact (not merely close)."""
    Cc = C // chunks
    x_cm = (send.reshape(N_ep, E_loc, C, d)
            .transpose(0, 2, 1, 3).reshape(N_ep * C * E_loc, d))
    acc = jnp.zeros((E_loc, N_ep, C, d),
                    jnp.promote_types(send.dtype, w_down.dtype))

    def consume(acc, y_c, i):
        tok_c = (y_c.reshape(N_ep, Cc, E_loc, d)
                 .transpose(2, 0, 1, 3).reshape(E_loc, N_ep * Cc, d))
        h = mlp.ACT[act](jnp.einsum("ecd,edf->ecf", tok_c, w_gate))
        h = h * jnp.einsum("ecd,edf->ecf", tok_c, w_up)
        ye_c = jnp.einsum("ecf,efd->ecd", h, w_down)
        return jax.lax.dynamic_update_slice_in_dim(
            acc, ye_c.reshape(E_loc, N_ep, Cc, d).astype(acc.dtype),
            i * Cc, axis=2)

    return mpix.mpix_alltoall_overlap(
        x_cm, ep, consume, acc, chunks=chunks,
        algorithm=opts.alltoall, policy=opts.policy,
        transport=opts.transport, resilience=opts.resilience)


def _dispatch_body(rp, w_gate, w_up, w_down, x, *, cfg: MoEConfig,
                   ep, opts: EPOptions, act):
    B, S, d = x.shape
    M = jax.lax.axis_size("model")
    m = jax.lax.axis_index("model")
    N_ep = 1
    for a in ep:
        N_ep *= jax.lax.axis_size(a)
    E, K = cfg.n_experts, cfg.top_k
    E_loc = E // N_ep
    T_total = B * S
    assert T_total % M == 0, (T_total, M)
    T = T_total // M

    # my 1/M token slice (tokens are replicated over the model axis)
    xt = x.reshape(T_total, d)
    xs = jax.lax.dynamic_slice_in_dim(xt, m * T, T, axis=0)

    w, idx, _ = moe.route(rp, cfg, xs)                        # [T,k]
    C = max(1, int(T * K / E * opts.capacity_factor))

    # bucket (token, slot) pairs into per-expert capacity slots
    flat_e = idx.reshape(-1)                                  # [T*K]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1,
                              flat_e[:, None], 1)[:, 0]
    keep = pos < C
    dest = jnp.where(keep, flat_e * C + pos, E * C)
    buckets = jnp.zeros((E * C + 1, d), x.dtype)
    buckets = buckets.at[dest].set(jnp.repeat(xs, K, axis=0))

    # ship buckets to expert owners (expert e lives on rank e // E_loc)
    send = buckets[: E * C]                                   # [E*C, d]
    k_ov = _overlap_chunks(opts, cfg=cfg, ep=ep, E_loc=E_loc,
                           N_ep=N_ep, C=C, d=d, f=w_gate.shape[2],
                           itemsize=x.dtype.itemsize)
    if k_ov >= 2:
        ye4 = _dispatch_overlapped(send, w_gate, w_up, w_down,
                                   chunks=k_ov, ep=ep, opts=opts,
                                   act=act, N_ep=N_ep, E_loc=E_loc,
                                   C=C, d=d)
    else:
        recv = mpix.mpix_alltoall(send, ep, algorithm=opts.alltoall,
                                  policy=opts.policy,
                                  transport=opts.transport,
                                  resilience=opts.resilience)
        tok = recv.reshape(N_ep, E_loc, C, d).transpose(1, 0, 2, 3) \
                  .reshape(E_loc, N_ep * C, d)

        h = mlp.ACT[act](jnp.einsum("ecd,edf->ecf", tok, w_gate))
        h = h * jnp.einsum("ecd,edf->ecf", tok, w_up)
        ye = jnp.einsum("ecf,efd->ecd", h, w_down)            # [E_loc,NC,d]
        ye4 = ye.reshape(E_loc, N_ep, C, d)

    back = ye4.transpose(1, 0, 2, 3).reshape(N_ep * E_loc * C, d)
    ret = mpix.mpix_alltoall(back, ep, algorithm=opts.alltoall,
                             policy=opts.policy,
                             transport=opts.transport,
                             resilience=opts.resilience)

    gathered = jnp.concatenate([ret, jnp.zeros((1, d), x.dtype)])[dest]
    out_slice = jnp.einsum("tkd,tk->td", gathered.reshape(T, K, d), w)

    # rebuild the full token set across the model axis
    out = mpix.mpix_allgather(out_slice, "model",
                              algorithm=opts.allgather,
                              policy=opts.policy,
                              transport=opts.transport,
                              resilience=opts.resilience)
    return out.reshape(B, S, d)
