"""Partitioned communication (paper §2.3, MPIPCL) — TPU adaptation.

MPIPCL channelizes a point-to-point message: one match at init, then the
buffer moves as P independently-committed *partitions*, letting transfer
of ready partitions overlap with production/consumption of the rest
("early-bird" communication).  MPIPCL inserts a progress thread because
MPI may not progress asynchronously; on TPU the compiler provides async
progress (collectives lower to start/done pairs), so the faithful
adaptation is *structural*: split the transfer into P chunks and
interleave chunk transfers with the producing/consuming compute inside
one program, giving XLA's scheduler the freedom the progress thread buys.

Three instantiations, mirroring how partitioned communication is used:

  * ``partitioned_ppermute``          — the raw primitive: chunked
    point-to-point with a per-partition consumer callback (receive-side
    early-bird: partitions are consumed as they arrive).
  * ``allgather_matmul``              — receive-side overlap in a
    collective: ring allgather where every arriving shard immediately
    feeds the MXU (x_aggregate @ w without waiting for the full gather).
  * ``matmul_reduce_scatter``         — send-side overlap ("early-bird
    send"): each output chunk is shipped as soon as it is computed,
    while the next chunk is being produced.
  * ``bucketed_psum``                 — gradient-sync form: a pytree is
    flattened into P buckets reduced independently, so XLA can overlap
    bucket k's all-reduce with the compute producing bucket k+1's grads
    (the classic DDP bucketing trick, expressed as partitioned comm).

All run inside ``shard_map``; all are differentiable (``ppermute``'s
transpose is the inverse permutation, so reverse-mode AD derives the
mirrored pipeline automatically).
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.schedule import CommSchedule, make_round
from repro.core.topology import Topology
from repro.core.transport import _flat_rank


def _axes_tuple(axis_names):
    return (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)


def _shift_perm(n: int, shift: int = 1) -> list[tuple[int, int]]:
    return [(i, (i + shift) % n) for i in range(n)]


# ---------------------------------------------------------------------------
# partitioned transfers on the unified IR
# ---------------------------------------------------------------------------


def partitioned_schedule(nranks: int, perm: Sequence[tuple[int, int]],
                         partitions: int = 1) -> CommSchedule:
    """A partitioned point-to-point transfer as a ``CommSchedule``.

    The working buffer has ``2 * partitions`` slots per rank: rows
    ``[0, P)`` hold the outgoing chunks, rows ``[P, 2P)`` receive.
    Round ``i`` ships chunk ``i`` along ``perm`` — MPIPCL's P
    independently-committed partitions, expressed in the same IR the
    dense and neighborhood collectives compile to (so the tuner can
    time the partition-count tradeoff like any other schedule).
    """
    P = int(partitions)
    if P < 1:
        raise ValueError(
            f"partitioned_schedule: partitions must be >= 1, got "
            f"{partitions}")
    edges = tuple((int(s), int(d)) for s, d in perm)
    rounds = []
    for i in range(P):
        send = {s: [i] for s, _ in edges}
        recv = {d: [P + i] for _, d in edges}
        rounds.append(make_round(nranks, edges, send, recv))
    return CommSchedule(
        nranks=nranks, num_slots=2 * P, rounds=tuple(rounds),
        name=f"partitioned.shift[p{P}]", out_slots=P,
        out_offsets=np.full(nranks, P, np.int64))


def _chunked_shift(topo: Topology, partitions: int) -> CommSchedule:
    return partitioned_schedule(topo.nranks, _shift_perm(topo.nranks),
                                partitions)


ALGORITHMS = {
    f"p{p}": functools.partial(_chunked_shift, partitions=p)
    for p in (1, 2, 4, 8)
}


# ---------------------------------------------------------------------------
# raw partitioned point-to-point
# ---------------------------------------------------------------------------


def partitioned_ppermute(x: jax.Array, axis_name, perm,
                         partitions: int,
                         consume: Callable[[jax.Array, jax.Array], jax.Array]
                         | None = None,
                         init=None, via: str = "scan"):
    """Send ``x`` along ``perm`` in ``partitions`` chunks (leading dim).

    Without ``consume``: returns the fully received buffer — semantically
    identical to one monolithic ppermute (the 1-partition case *is* the
    monolithic transfer, the paper's "no worse than base pt2pt" claim).
    ``via="schedule"`` lowers this path through the unified
    ``CommSchedule`` IR + ``ShardMapTransport`` instead of a scan
    (identical result; lets the tuner time it like any collective).

    With ``consume(carry, chunk) -> carry``: receive-side early-bird —
    each arriving partition is folded into ``carry`` immediately; chunk
    i+1's transfer overlaps chunk i's consumption (XLA schedules the
    next ppermute-start before the consume of the previous done).
    """
    if partitions <= 0:
        raise ValueError(
            f"partitioned_ppermute: partitions must be >= 1, got "
            f"{partitions}")
    if x.shape[0] % partitions:
        raise ValueError(
            f"partitioned_ppermute: leading dim {x.shape[0]} of input "
            f"shape {tuple(x.shape)} must be divisible by "
            f"partitions={partitions}")
    chunk = x.shape[0] // partitions
    chunks = x.reshape((partitions, chunk) + x.shape[1:])

    if consume is None:
        if via == "schedule":
            from repro.core.transport import ShardMapTransport
            names = _axes_tuple(axis_name)
            n = 1
            for a in names:
                n *= jax.lax.axis_size(a)
            sched = partitioned_schedule(n, perm, partitions)
            buf = jnp.concatenate([chunks, jnp.zeros_like(chunks)], axis=0)
            out = ShardMapTransport(n, names).run(sched, buf)
            return out[partitions:].reshape(x.shape)
        def body(_, c):
            return None, jax.lax.ppermute(c, axis_name, perm)
        _, out = jax.lax.scan(body, None, chunks)
        return out.reshape(x.shape)

    def body(carry, c):
        arrived = jax.lax.ppermute(c, axis_name, perm)
        return consume(carry, arrived), None

    carry, _ = jax.lax.scan(body, init, chunks)
    return carry


# ---------------------------------------------------------------------------
# receive-side overlap: allgather-matmul (collective matmul)
# ---------------------------------------------------------------------------


def allgather_matmul(x: jax.Array, w: jax.Array, axis_name, *,
                     partitions_per_rank: int = 1,
                     precision=None) -> jax.Array:
    """``all_gather(x) @ w`` as a ring pipeline: each ring step's arriving
    shard is matmul'd while the next shard is in flight.

    x: [m_local, k] (this rank's shard of the row dimension)
    w: [k, n] (replicated over ``axis_name``)
    returns [m_local * axis_size, n] — bitwise layout of the unfused op.
    """
    names = _axes_tuple(axis_name)
    n_ranks = 1
    for a in names:
        n_ranks *= jax.lax.axis_size(a)
    axis_arg = names if len(names) > 1 else names[0]
    rank = _flat_rank(names)
    m_local = x.shape[0]
    out = jnp.zeros((n_ranks, m_local, w.shape[1]),
                    jnp.promote_types(x.dtype, w.dtype))
    # ring: at step t we hold the shard of rank (rank + t) mod n
    perm = _shift_perm(n_ranks, -1 % n_ranks)  # pass shards backwards

    def body(carry, t):
        buf, acc = carry
        src = (rank + t) % n_ranks
        prod = _chunked_matmul(buf, w, partitions_per_rank, precision)
        acc = acc.at[src].set(prod.astype(acc.dtype))
        nxt = jax.lax.ppermute(buf, axis_arg, perm)
        return (nxt, acc), None

    (_, out), _ = jax.lax.scan(body, (x, out), jnp.arange(n_ranks))
    return out.reshape(n_ranks * m_local, w.shape[1])


def _chunked_matmul(x, w, parts, precision):
    if parts <= 1 or x.shape[0] % parts:
        return jnp.dot(x, w, precision=precision)
    xs = x.reshape((parts, x.shape[0] // parts) + x.shape[1:])
    return jax.lax.map(
        lambda c: jnp.dot(c, w, precision=precision), xs
    ).reshape(x.shape[0], w.shape[1])


# ---------------------------------------------------------------------------
# send-side overlap: matmul-reduce-scatter
# ---------------------------------------------------------------------------


def matmul_reduce_scatter(x: jax.Array, w: jax.Array, axis_name, *,
                          precision=None) -> jax.Array:
    """``psum_scatter(x @ w)`` as a ring pipeline: output chunk for rank
    r+t is computed at step t and immediately enters the reduction ring
    while the next chunk is being produced (early-bird send).

    x: [m, k_local]  w: [k_local, n]   (k contracted over ``axis_name``)
    returns this rank's [m / n_ranks, n] reduced scatter shard.
    """
    names = _axes_tuple(axis_name)
    n_ranks = 1
    for a in names:
        n_ranks *= jax.lax.axis_size(a)
    axis_arg = names if len(names) > 1 else names[0]
    rank = _flat_rank(names)
    m = x.shape[0]
    assert m % n_ranks == 0
    mc = m // n_ranks
    xs = x.reshape(n_ranks, mc, x.shape[1])
    perm = _shift_perm(n_ranks, 1)

    def body(acc, t):
        # at step t every rank computes + forwards the partial of chunk
        # (rank - t); after n-1 hops the full sum of chunk r sits on rank r.
        idx = (rank - t) % n_ranks
        mine = jnp.dot(xs[idx], w, precision=precision)
        acc = acc + mine
        acc = jax.lax.ppermute(acc, axis_arg, perm)
        return acc, None

    acc = jnp.zeros((mc, w.shape[1]), jnp.promote_types(x.dtype, w.dtype))
    # n-1 compute+shift steps, then a final local compute (own chunk):
    # the traveling accumulator for chunk c starts at rank c+1 and visits
    # the ring in +1 order, so rank r touches chunk (r - t) at step t.
    acc, _ = jax.lax.scan(body, acc, jnp.arange(1, n_ranks))
    acc = acc + jnp.dot(xs[rank], w, precision=precision)
    return acc


# ---------------------------------------------------------------------------
# gradient bucketing (partitioned allreduce over a pytree)
# ---------------------------------------------------------------------------


def bucketed_psum(tree, axis_names, *, buckets: int = 4):
    """psum a pytree in ``buckets`` independent flat buckets.

    Equality with ``jax.tree.map(psum)`` is exact; the point is schedule
    freedom: each bucket's all-reduce is an independent collective XLA
    can overlap with the compute producing later buckets' inputs.
    """
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    sizes = [l.size for l in leaves]
    dtype = jnp.result_type(*[l.dtype for l in leaves])
    flat = jnp.concatenate([l.reshape(-1).astype(dtype) for l in leaves])
    total = flat.size
    per = -(-total // buckets)
    pad = per * buckets - total
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), dtype)])
    parts = flat.reshape(buckets, per)
    reduced = [jax.lax.psum(parts[i], _axes_tuple(axis_names))
               for i in range(buckets)]
    flat = jnp.concatenate(reduced)[:total]
    out, off = [], 0
    for l, s in zip(leaves, sizes):
        out.append(flat[off: off + s].reshape(l.shape).astype(l.dtype))
        off += s
    return jax.tree.unflatten(treedef, out)
