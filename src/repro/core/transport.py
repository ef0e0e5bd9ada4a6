"""Transport backends that execute a ``CommSchedule`` (see schedule.py).

MPI Advance writes every collective algorithm once, against MPI point-to-
point primitives, and runs it on any substrate.  We keep the same split:
one IR (``CommSchedule``: gather tables -> static permutation -> scatter
tables), three executors:

  * ``SimTransport``      — numpy, rank-by-rank.  Bit-exact execution of
                            a schedule for N simulated ranks on zero
                            devices.  Used by unit/property tests and by
                            the message/byte accounting benchmarks.
  * ``ShardMapTransport`` — the production substrate: each ``CommRound``
                            becomes one ``jax.lax.ppermute`` (the TPU ICI
                            point-to-point primitive) inside ``shard_map``.
  * ``PallasTransport``   — device-side: the WHOLE compiled schedule as
                            ONE Pallas kernel (core.pallas_lowering) —
                            launch amortization for alpha-dominated
                            message sizes (the paper's GPU-aware pillar).

Dense collectives, neighborhood alltoallv plans, and partitioned
transfers all execute through these classes — there is exactly one
execution semantics to keep bit-identical.

Buffers are slot-indexed: the working array has shape
``[num_slots + 1, *slot_shape]`` on every rank — the final slot is a
scratch row that absorbs sends/receives masked out with ``-1`` in the
schedule tables, so execution is fully static (no data-dependent control
flow, as required for TPU lowering).

Since the persistent-executor compilation (core.executor) both ``run``
methods are thin lookups: the schedule is lowered once to a cached
``CompiledExec`` (tables baked, rounds fused, locals folded) and every
subsequent call — every training step, every tuner repeat — reuses it,
the MPI-4 persistent-collective split.  ``SimTransport.run_reference``
keeps the original rank-by-rank loop as the executor's oracle.
"""
from __future__ import annotations

import abc
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import executor
from repro.core.schedule import CommRound, CommSchedule
from repro.core.topology import Topology

from repro import compat


class TransportError(RuntimeError):
    """A substrate failed to execute a round/schedule (a failed kernel
    launch, a dropped ppermute, an injected chaos fault).  Typed so the
    recovery ladder (``core.resilient``) can distinguish a *transport*
    failure — retryable, degradable to another substrate — from a
    programming error, which must stay loud.

    ``transport`` names the substrate, ``round_idx`` the failing round
    (-1 when the failure is not round-attributable)."""

    def __init__(self, msg: str, *, transport: str = "?",
                 round_idx: int = -1):
        super().__init__(msg)
        self.transport = transport
        self.round_idx = round_idx


class Transport(abc.ABC):
    """Executes schedules for a fixed rank count.

    An optional ``topo`` arms the persistent-executor compile pass with
    the alpha-beta cost model (multi-target fusion + round reordering,
    see core.executor); without one the topology-free single-target
    rule runs.  The executor cache keys on the topology fingerprint, so
    one transport per geometry never collides with another.
    """

    nranks: int
    topo: Topology | None

    @abc.abstractmethod
    def run(self, schedule: CommSchedule, buf):
        """Execute ``schedule`` on a slot-indexed buffer and return it."""


# ---------------------------------------------------------------------------
# numpy simulator
# ---------------------------------------------------------------------------


class SimTransport(Transport):
    """Rank-by-rank numpy execution: ``buf`` is [nranks, num_slots, *slot].

    Exact semantics match ShardMapTransport:
      * a rank that is not a destination in a round receives zeros,
      * gather index -1 sends zeros,
      * scatter index -1 drops the received slot,
      * ``reduce=True`` accumulates (+=) instead of overwriting,
      * (r, r) self-pairs deliver the rank's own payload (on-chip copy).
    """

    def __init__(self, nranks: int, topo: Topology | None = None):
        self.nranks = nranks
        self.topo = topo

    def run(self, schedule: CommSchedule, buf: np.ndarray) -> np.ndarray:
        """Compiled-path execution: one vectorized gather/permute/scatter
        per round through the cached ``CompiledExec`` (no per-rank or
        per-slot Python loops — what keeps ``tuner.autotune`` and the
        bit-exactness sweeps fast)."""
        assert buf.shape[0] == self.nranks, (buf.shape, self.nranks)
        assert buf.shape[1] == schedule.num_slots
        return executor.get_executor(schedule, topo=self.topo).run_sim(buf)

    def run_chunked(self, schedule: CommSchedule, buf: np.ndarray, *,
                    chunks: int, consume=None, init=None):
        """Row-chunked (partitioned) execution: split the slot row axis
        into ``chunks`` equal pieces, run the full schedule per piece,
        and fold each piece's output through ``consume(carry, out, i)``
        as soon as it lands — the MPIPCL shape where chunk ``i+1``'s
        transfer overlaps chunk ``i``'s consumer compute.

        With ``consume=None`` the chunk outputs are reassembled and the
        result is bit-identical to ``run`` (each chunk sees a disjoint
        row slice; schedules never mix rows).  ``buf`` is
        [nranks, num_slots, rows, ...]; ``rows`` must divide by
        ``chunks``."""
        if chunks <= 0:
            raise ValueError(f"run_chunked: chunks must be >= 1, "
                             f"got {chunks}")
        assert buf.ndim >= 3, buf.shape
        rows = buf.shape[2]
        if rows % chunks:
            raise ValueError(
                f"run_chunked: row count {rows} is not divisible by "
                f"chunks={chunks}")
        rc = rows // chunks
        carry = init
        outs = []
        for i in range(chunks):
            piece = np.ascontiguousarray(
                buf[:, :, i * rc:(i + 1) * rc])
            out = self.run(schedule, piece)
            if consume is None:
                outs.append(out)
            else:
                carry = consume(carry, out, i)
        if consume is None:
            return np.concatenate(outs, axis=2)
        return carry

    def run_reference(self, schedule: CommSchedule,
                      buf: np.ndarray) -> np.ndarray:
        """The original rank-by-rank loop — kept as the semantic oracle
        the compiled/fused path is tested bit-exact against."""
        assert buf.shape[0] == self.nranks, (buf.shape, self.nranks)
        assert buf.shape[1] == schedule.num_slots
        buf = buf.copy()
        if schedule.local_pre is not None:
            buf = np.stack([buf[r, schedule.local_pre[r]]
                            for r in range(self.nranks)])
        for rnd in schedule.rounds:
            buf = self._round(rnd, buf)
        if schedule.local_post is not None:
            buf = np.stack([buf[r, schedule.local_post[r]]
                            for r in range(self.nranks)])
        return buf

    def _round(self, rnd: CommRound, buf: np.ndarray) -> np.ndarray:
        slot_shape = buf.shape[2:]
        # Everyone starts this round receiving zeros (ppermute semantics).
        inbox = np.zeros((self.nranks, rnd.k) + slot_shape, buf.dtype)
        for src, dst in rnd.perm:
            gather = rnd.gather_idx[src]
            payload = np.zeros((rnd.k,) + slot_shape, buf.dtype)
            valid = gather >= 0
            payload[valid] = buf[src, gather[valid]]
            inbox[dst] = payload
        out = buf.copy()
        dst_set = {d for _, d in rnd.perm}
        for r in range(self.nranks):
            if r not in dst_set:
                continue
            scatter = rnd.scatter_idx[r]
            for slot in range(rnd.k):
                tgt = scatter[slot]
                if tgt < 0:
                    continue
                if rnd.reduce:
                    out[r, tgt] = out[r, tgt] + inbox[r, slot]
                else:
                    out[r, tgt] = inbox[r, slot]
        return out


# ---------------------------------------------------------------------------
# shard_map substrate
# ---------------------------------------------------------------------------


def _flat_rank(axis_names: Sequence[str]):
    """Row-major flattened rank over possibly-multiple mesh axes."""
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


class ShardMapTransport(Transport):
    """Executes schedules with ``ppermute`` inside an ambient ``shard_map``.

    ``run`` must be called from *inside* a shard_map whose manual axes
    include ``axis_names`` (row-major order defines the flat rank, matching
    the CommSchedule's rank numbering).  ``buf`` here is the *local*
    buffer, shape [num_slots, *slot], and one scratch slot is appended
    internally.
    """

    def __init__(self, nranks: int, axis_names: Sequence[str] | str,
                 topo: Topology | None = None):
        self.nranks = nranks
        self.topo = topo
        self.axis_names = ((axis_names,) if isinstance(axis_names, str)
                           else tuple(axis_names))

    def run(self, schedule: CommSchedule, buf: jax.Array) -> jax.Array:
        """Compiled-path execution: look up the cached ``CompiledExec``
        (tables already on device, rounds fused — cost-model-armed when
        this transport carries a topology) and trace its rounds.  The
        executor's trace counter makes the persistence observable:
        repeated jitted calls with one (shape, dtype) lower exactly
        once."""
        assert buf.shape[0] == schedule.num_slots
        rank = _flat_rank(self.axis_names)
        return executor.get_executor(schedule, topo=self.topo).run_shardmap(
            buf, rank, self._axis_arg())

    def run_chunked(self, schedule: CommSchedule, buf: jax.Array, *,
                    chunks: int, consume=None, init=None):
        """Row-chunked (partitioned) execution under ``lax.scan``: the
        local buffer [num_slots, rows, ...] is split along the row axis
        into ``chunks`` equal pieces and the full schedule runs once per
        piece through ONE cached executor — a single trace regardless of
        chunk count (double-buffered chunk loop; the scheduler overlaps
        chunk ``i+1``'s ppermutes with chunk ``i``'s ``consume``
        compute).  With ``consume=None`` the outputs reassemble to
        exactly ``run``'s result; otherwise the final
        ``consume(carry, out, i)`` carry is returned."""
        if chunks <= 0:
            raise ValueError(f"run_chunked: chunks must be >= 1, "
                             f"got {chunks}")
        assert buf.ndim >= 2, buf.shape
        slots, rows = buf.shape[0], buf.shape[1]
        if rows % chunks:
            raise ValueError(
                f"run_chunked: row count {rows} is not divisible by "
                f"chunks={chunks}")
        rc = rows // chunks
        tail = buf.shape[2:]
        # [slots, rows, ...] -> [chunks, slots, rc, ...] scan leaves
        xs = buf.reshape((slots, chunks, rc) + tail).swapaxes(0, 1)
        if consume is None:
            def body(_, xc):
                return None, self.run(schedule, xc)
            _, ys = jax.lax.scan(body, None, xs)
            return (ys.swapaxes(0, 1)
                    .reshape((slots, rows) + tail))

        def body(carry, xi):
            xc, i = xi
            return consume(carry, self.run(schedule, xc), i), None
        carry, _ = jax.lax.scan(
            body, init, (xs, jnp.arange(chunks, dtype=jnp.int32)))
        return carry

    def run_global(self, schedule: CommSchedule, gbuf) -> jax.Array:
        """Host-side execution of a *global* [nranks, num_slots, *slot]
        buffer: builds a one-axis mesh over the first ``nranks`` local
        devices and runs the schedule inside its own ``shard_map`` —
        the PallasTransport.run_global calling convention on the
        ppermute substrate.  This is the entry the recovery ladder
        (``core.resilient``) and the tuner use when they hold concrete
        buffers rather than traced shards; requires ``nranks`` devices
        (``TransportError`` otherwise, so the ladder can skip the rung
        instead of crashing)."""
        from jax.sharding import PartitionSpec as P

        n = self.nranks
        if jax.device_count() < n:
            raise TransportError(
                f"shardmap run_global needs {n} devices, have "
                f"{jax.device_count()}", transport="shardmap")
        assert gbuf.shape[0] == n, (gbuf.shape, n)
        assert gbuf.shape[1] == schedule.num_slots
        mesh = compat.make_mesh((n,), ("_resil",),
                                devices=jax.devices()[:n])
        tr = ShardMapTransport(n, "_resil", topo=self.topo)
        f = compat.shard_map(
            lambda b: tr.run(schedule, b), mesh=mesh,
            in_specs=P("_resil"), out_specs=P("_resil"), check_vma=False)
        flat = jnp.asarray(gbuf).reshape((n * schedule.num_slots,)
                                         + gbuf.shape[2:])
        out = f(flat)
        return out.reshape((n, schedule.num_slots) + gbuf.shape[2:])

    def _axis_arg(self):
        return self.axis_names if len(self.axis_names) > 1 else self.axis_names[0]


# ---------------------------------------------------------------------------
# device-side Pallas substrate
# ---------------------------------------------------------------------------


class PallasTransport(Transport):
    """Device-side execution: the WHOLE compiled schedule as ONE Pallas
    kernel (core.pallas_lowering), instead of one ppermute launch per
    round.

    The kernel runs on the *global* slot buffer [nranks, num_slots,
    *slot].  Used standalone (``run_global``, the SimTransport calling
    convention — what the bit-exactness sweeps drive), or inside a
    shard_map (``run``, the ShardMapTransport calling convention): the
    local buffers are first combined with a single ``all_gather``, every
    rank executes the kernel on the replicated global buffer
    (deterministic, so all ranks agree bit-for-bit), and each keeps its
    own row.  That trades bandwidth (the gather ships n× the data) for
    launches (1 collective + 1 kernel vs R collectives) — the
    alpha/beta crossover the tuner's ``transport`` policy cell prices
    per size bucket.  On multi-chip TPU topologies the same kernel
    structure extends to RDMA rounds without the gather; that variant
    is TPU-gated (see pallas_lowering).
    """

    def __init__(self, nranks: int,
                 axis_names: Sequence[str] | str | None = None,
                 topo: Topology | None = None):
        self.nranks = nranks
        self.topo = topo
        if axis_names is None:
            self.axis_names = None
        else:
            self.axis_names = ((axis_names,) if isinstance(axis_names, str)
                               else tuple(axis_names))

    def run_global(self, schedule: CommSchedule, gbuf, *, chunks: int = 1):
        """Execute on a global [nranks, num_slots, *slot] buffer — one
        kernel launch; ``chunks > 1`` tiles the slot row axis over the
        Pallas grid (double-buffered block pipeline, bit-identical)."""
        from repro.core.pallas_lowering import get_pallas_exec
        assert gbuf.shape[0] == self.nranks, (gbuf.shape, self.nranks)
        assert gbuf.shape[1] == schedule.num_slots
        return get_pallas_exec(schedule, topo=self.topo).run(
            gbuf, chunks=chunks)

    def run(self, schedule: CommSchedule, buf: jax.Array) -> jax.Array:
        """Called from inside a shard_map over ``axis_names`` with the
        *local* buffer [num_slots, *slot]; returns the local result."""
        if self.axis_names is None:
            raise ValueError(
                "PallasTransport.run needs axis_names (inside shard_map); "
                "use run_global for host-side global-buffer execution")
        # leading gathered axis is row-major over the name tuple — the
        # same order as _flat_rank, so gbuf[r] is rank r's local buffer
        gbuf = jax.lax.all_gather(buf, self._axis_arg())
        gbuf = gbuf.reshape((self.nranks,) + buf.shape)
        out = self.run_global(schedule, gbuf)
        return jax.lax.dynamic_index_in_dim(
            out, _flat_rank(self.axis_names), axis=0, keepdims=False)

    def run_chunked(self, schedule: CommSchedule, buf: jax.Array, *,
                    chunks: int, consume=None, init=None):
        """Row-chunked execution inside shard_map.  With ``consume=None``
        the chunking collapses into the kernel itself (grid tiling — one
        launch, same as ``run``); with a consumer the pieces run through
        a ``lax.scan`` so chunk ``i``'s ``consume`` compute overlaps
        chunk ``i+1``'s gather+kernel, mirroring ShardMapTransport."""
        if chunks <= 0:
            raise ValueError(f"run_chunked: chunks must be >= 1, "
                             f"got {chunks}")
        assert buf.ndim >= 2, buf.shape
        slots, rows = buf.shape[0], buf.shape[1]
        if rows % chunks:
            raise ValueError(
                f"run_chunked: row count {rows} is not divisible by "
                f"chunks={chunks}")
        if consume is None:
            if self.axis_names is None:
                raise ValueError(
                    "PallasTransport.run_chunked needs axis_names")
            gbuf = jax.lax.all_gather(buf, self._axis_arg())
            gbuf = gbuf.reshape((self.nranks,) + buf.shape)
            out = self.run_global(schedule, gbuf, chunks=chunks)
            return jax.lax.dynamic_index_in_dim(
                out, _flat_rank(self.axis_names), axis=0, keepdims=False)
        rc = rows // chunks
        tail = buf.shape[2:]
        xs = buf.reshape((slots, chunks, rc) + tail).swapaxes(0, 1)

        def body(carry, xi):
            xc, i = xi
            return consume(carry, self.run(schedule, xc), i), None
        carry, _ = jax.lax.scan(
            body, init, (xs, jnp.arange(chunks, dtype=jnp.int32)))
        return carry

    def _axis_arg(self):
        return (self.axis_names if len(self.axis_names) > 1
                else self.axis_names[0])
