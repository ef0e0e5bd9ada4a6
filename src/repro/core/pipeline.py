"""Pipeline parallelism over a mesh axis (multi-pod strategy).

The pod boundary is a natural pipeline cut: DCN carries only the
activations of one microbatch per step (tiny vs. gradient allreduce).
This module provides a GPipe-style schedule written once in ``shard_map``
terms: every stage runs the same program; activations advance with a
static ``ppermute``; reverse-mode AD differentiates through the schedule
(the transpose of ``ppermute`` is the reverse shift), so one forward
definition yields the full fwd+bwd pipeline.

The schedule runs T = M + S - 1 ticks for M microbatches over S stages
(classic GPipe bubble of (S-1)/(M+S-1)); stage s computes microbatch m
at tick t = m + s.  Inputs are consumed on stage 0, outputs collected on
stage S-1 (and shipped back to stage 0 if ``return_to_first``).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def gpipe(stage_fn: Callable, params, x_ubatches: jax.Array,
          axis_name: str, *, return_to_first: bool = False) -> jax.Array:
    """Run ``stage_fn(params, x) -> y`` as an S-stage pipeline.

    Call inside ``shard_map``; ``axis_name`` is the pipeline axis.
      params:      this stage's parameters (already sharded over stages).
      x_ubatches:  [M, ub, ...] microbatch stream; only stage 0's copy is
                   read (other stages may carry zeros).
    Returns [M, ub, ...] outputs, valid on the last stage (or stage 0 if
    ``return_to_first``); other stages see zeros.
    """
    S = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = x_ubatches.shape[0]
    T = M + S - 1
    fwd = [(i, (i + 1) % S) for i in range(S)]

    state = jnp.zeros_like(x_ubatches[0])          # activation in flight
    ybuf = jnp.zeros((M,) + x_ubatches.shape[1:], x_ubatches.dtype)

    def tick(carry, t):
        state, ybuf = carry
        # stage 0 ingests microbatch t while it still has fresh ones
        m_in = jnp.clip(t, 0, M - 1)
        state = jnp.where(stage == 0, x_ubatches[m_in], state)
        y = stage_fn(params, state)
        # last stage banks microbatch m = t - (S - 1) when in range
        m_out = t - (S - 1)
        take = (stage == S - 1) & (m_out >= 0)
        ybuf = jax.lax.cond(
            take,
            lambda b: jax.lax.dynamic_update_index_in_dim(
                b, y.astype(b.dtype), jnp.clip(m_out, 0, M - 1), 0),
            lambda b: b, ybuf)
        # advance the wavefront (stage S-1 -> 0 wrap carries garbage that
        # stage 0 immediately overwrites with the next ingest)
        state = jax.lax.ppermute(y, axis_name, fwd)
        return (state, ybuf), None

    (_, ybuf), _ = jax.lax.scan(tick, (state, ybuf), jnp.arange(T))
    if return_to_first:
        ybuf = jax.lax.ppermute(ybuf, axis_name, [(S - 1, 0)])
    return ybuf


def stage_params_spec(n_layers: int, n_stages: int) -> list[range]:
    """Contiguous layer ranges per stage (remainder to the last stages)."""
    base, rem = divmod(n_layers, n_stages)
    out, start = [], 0
    for s in range(n_stages):
        k = base + (1 if s >= n_stages - rem else 0)
        out.append(range(start, start + k))
        start += k
    assert start == n_layers
    return out


# ---------------------------------------------------------------------------
# makespan-model view of the GPipe schedule (shared compute_events IR)
# ---------------------------------------------------------------------------


def gpipe_compute_events(n_microbatches: int, n_stages: int,
                         stage_seconds: float) -> tuple:
    """The pipeline's per-tick compute as executor ``ComputeEvent``s:
    tick ``t`` of the T = M + S - 1 wavefront is one opaque costed
    block of ``stage_seconds`` anchored after shift round ``t`` — the
    same vocabulary MoE dispatch and the grad-sync overlap register
    their consumer compute with, so the makespan model prices GPipe
    like any other pipelined schedule."""
    from repro.core.schedule import ComputeEvent

    T = n_microbatches + n_stages - 1
    return tuple(ComputeEvent(f"tick{t}", float(stage_seconds),
                              after_round=t) for t in range(T))


def gpipe_wavefront_schedule(n_microbatches: int, n_stages: int,
                             stage_seconds: float):
    """The GPipe wavefront as a ``CommSchedule`` + compute events.

    One ring-shift round per tick (the ``ppermute`` advancing the
    activation in flight) with a ``ComputeEvent`` per tick for the
    stage compute.  Consecutive shifts reuse the same slot (RAW), so
    no compaction pass may fuse them — the armed executor's makespan
    therefore reproduces the classic pipeline cost
    ``shift + sum(max(shift, compute)) + compute`` instead of the
    serial sum, without any GPipe-specific pricing code."""
    import numpy as np

    from repro.core.schedule import CommSchedule, make_round

    M, S = int(n_microbatches), int(n_stages)
    if M < 1 or S < 1:
        raise ValueError(
            f"gpipe_wavefront_schedule: need n_microbatches >= 1 and "
            f"n_stages >= 1, got {n_microbatches}, {n_stages}")
    T = M + S - 1
    edges = tuple((i, (i + 1) % S) for i in range(S))
    send = {s: [0] for s, _ in edges}
    recv = {d: [0] for _, d in edges}
    rounds = tuple(make_round(S, edges, send, recv) for _ in range(T))
    return CommSchedule(
        nranks=S, num_slots=1, rounds=rounds,
        name=f"gpipe.wavefront[m{M}.s{S}]",
        compute_events=gpipe_compute_events(M, S, stage_seconds))
