"""Device-side Pallas lowering of a ``CompiledExec`` (the paper's
GPU-aware pillar): the WHOLE compiled round sequence as ONE kernel.

Both other transports lower every compiled ``CommRound`` to a
gather-permute-scatter around ``shard_map``/``ppermute``, so an R-round
schedule pays R XLA collective launches.  This module takes the baked
numpy index tables of a ``CompiledExec`` (``_ExecRound.src/dst/g_safe/
g_mask/t_safe/t_mask`` plus the folded local pre/post permutations) and
emits them as static slot copies in a single ``pl.pallas_call`` over the
*global* slot buffer ``[nranks, num_slots, *slot]``:

  * each slot is laid out as rows of 128 lanes (``[rows, 128]``, zero
    padded), the TPU's native tile, so every slot move is a whole-tile
    VMEM copy; indices are Python ints (Pallas kernels cannot capture
    array constants), so ``-1`` routes simply emit nothing;
  * each round runs in two phases that keep ppermute semantics exactly:
    phase 1 stages into an inbox scratch every payload whose source slot
    the round overwrites (so it is read from the pre-round state), phase
    2 lands every write through the work ref — an overwrite, or for
    reduce rounds ``work + payload`` in the simulator's add order, which
    keeps the result bitwise equal to ``SimTransport.run_reference``;
  * the row axis is tiled onto the Pallas grid: ``chunks`` sets the
    least number of grid steps, and a buffer whose blocks would not fit
    ``VMEM_BUDGET`` is cut into more steps.  Rows never mix, so every
    tiling is bit-identical; grid pipelining double-buffers the block
    transfers.  Still one kernel launch.  A schedule with so many slots
    that one (8, 128) tile per slot exceeds the budget raises a typed
    ``TransportError`` that names the bound.

R rounds -> 1 launch is the whole point: ``PallasExec.launches`` counts
launches so the benchmark can assert the amortization (R -> 1 over the
corpus).  On a TPU the kernel is compiled by Mosaic; where no TPU backs
the process (the CPU test suite) it runs under the Pallas interpreter
(``kernels.compat.pallas_interpret``), with the same bitwise contract.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.executor import CompiledExec, get_executor
from repro.core.schedule import CommSchedule, validate_schedules_enabled
from repro.core.topology import Topology
from repro.core.transport import TransportError
from repro.kernels.compat import pallas_interpret


# Scoped VMEM the kernel asks Mosaic for (a v5e core has 128 MiB), and
# the part of it the block plan may fill: two buffers each of the in and
# out blocks, the work scratch and the inbox.  The rest is headroom for
# Mosaic's own scratch.
VMEM_LIMIT = 64 << 20
VMEM_BUDGET = 48 << 20
_LANES = 128
_MAX_BLOCK_ROWS = 512        # rows of 128 lanes per slot per grid step


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _sublanes(itemsize: int) -> int:
    """Rows of one native tile: (8, 128) at 32 bits, (16, 128) at 16."""
    return 8 * max(1, 4 // itemsize)


def _round_plan(rnd):
    """(live, staged) of one compiled round: the live (edge, slot)
    writes in order, and the subset whose source slot the round itself
    overwrites — those payloads are staged in the inbox first."""
    live = [(e, j) for e in range(len(rnd.src)) for j in range(rnd.k)
            if rnd.t_mask[e, j]]
    written = {(int(rnd.dst[e]), int(rnd.t_safe[e, j])) for e, j in live}
    staged = [(e, j) for e, j in live if rnd.g_mask[e, j]
              and (int(rnd.src[e]), int(rnd.g_safe[e, j])) in written]
    return live, staged


class PallasExec:
    """One ``CompiledExec`` lowered to a single-kernel Pallas executor.

    ``run(gbuf, chunks=)`` executes the full schedule (local_pre ->
    every compiled round -> local_post) on a global buffer
    ``[nranks, num_slots, *slot]`` and returns the same shape — the
    ``SimTransport`` calling convention, which is what lets the
    ``run_reference`` oracle check it bit-for-bit.  ``launches`` counts
    ``pallas_call`` invocations (one per ``run``, regardless of round
    count R); ``jit_traces`` counts actual lowerings (one per (shape,
    dtype, grid) thanks to the jit cache — the persistent-collective
    property, same contract as ``CompiledExec.trace_count``).
    """

    def __init__(self, ex: CompiledExec, *, interpret: bool | None = None):
        self.ex = ex
        self.nranks = ex.nranks
        self.num_slots = ex.num_slots
        self.rounds = ex.rounds_after
        self.interpret = (pallas_interpret() if interpret is None
                          else bool(interpret))
        self._plans = [_round_plan(r) for r in ex._rounds]
        self.inbox_slots = max([1] + [len(st) for _, st in self._plans])
        # slot-sized VMEM blocks: 2 in + 2 out buffers, work (only when a
        # local_post permutation needs a copy apart from the output) and
        # the inbox
        n_s = self.nranks * self.num_slots
        self.vmem_slot_blocks = ((4 + (ex._post is not None)) * n_s
                                 + self.inbox_slots)
        self.launches = 0
        self.jit_traces = 0
        self._jitted: dict = {}

    @property
    def min_vmem_bytes(self) -> int:
        """VMEM of the smallest legal block: one native tile (4 KiB at
        any dtype) per slot block."""
        return self.vmem_slot_blocks * 8 * _LANES * 4

    @property
    def fits(self) -> bool:
        return self.min_vmem_bytes <= VMEM_BUDGET

    # -- kernel body ------------------------------------------------------
    def _kernel(self, in_ref, out_ref, *scratch):
        """Executes on refs shaped [n, s, rows, 128].

        Every index comes from the baked numpy tables as a Python int,
        so the whole routing program is kernel-resident and each slot
        move is a whole-tile VMEM copy (no dynamic gather, no scatter).
        ``-1`` routes (masked slots) emit nothing."""
        self.jit_traces += 1
        ex = self.ex
        n, s = self.nranks, self.num_slots
        *work, inbox = scratch
        # without local_post the output block is the work buffer
        buf = work[0] if work else out_ref
        # stage in + local_pre fold (non-bijective pre survives folding)
        if ex._pre is None:
            buf[...] = in_ref[...]
        else:
            for r in range(n):
                for i in range(s):
                    buf[r, i] = in_ref[r, int(ex._pre[r, i])]
        zero = jnp.zeros(buf.shape[2:], buf.dtype)       # one slot block
        for rnd, (live, staged) in zip(ex._rounds, self._plans):
            def payload(e, j, rnd=rnd):
                if not rnd.g_mask[e, j]:
                    return zero                  # masked gathers send 0
                return buf[int(rnd.src[e]), int(rnd.g_safe[e, j])]

            # phase 1 — stage the payloads whose source slot this round
            # overwrites, so they are read from the PRE-round state
            # (ppermute semantics: no write of a round is visible to
            # any of its reads); every other payload is read in phase 2
            box = {}
            for e, j in staged:
                box[e, j] = len(box)
                inbox[box[e, j]] = payload(e, j)
            # phase 2 — land every write.  The masked-gather zero adds
            # are kept: bit-parity with run_sim (x + 0.0 normalizes
            # -0.0; chained adds in j order match np.add.at element
            # order even for duplicate targets).
            for e, j in live:
                dst, t = int(rnd.dst[e]), int(rnd.t_safe[e, j])
                val = inbox[box[e, j]] if (e, j) in box else payload(e, j)
                buf[dst, t] = buf[dst, t] + val if rnd.reduce else val
        # local_post + drain
        if ex._post is not None:
            for r in range(n):
                for i in range(s):
                    out_ref[r, i] = buf[r, int(ex._post[r, i])]

    # -- launch -----------------------------------------------------------
    def _plan(self, elems: int, itemsize: int, chunks: int):
        """(rows, block_rows): the slot's 128-lane row count after
        padding, and the rows of one grid step."""
        sub = _sublanes(itemsize)
        rows = _cdiv(elems, _LANES)
        per_row = self.vmem_slot_blocks * _LANES * itemsize
        max_rows = min(_MAX_BLOCK_ROWS, VMEM_BUDGET // per_row // sub * sub)
        if max_rows < sub:
            raise TransportError(
                f"pallas transport: {self.ex.schedule.name} needs "
                f"{self.min_vmem_bytes} B of VMEM for one ({sub}, "
                f"{_LANES}) tile in each of its {self.vmem_slot_blocks} "
                f"slot blocks, over the {VMEM_BUDGET} B bound "
                f"(VMEM_BUDGET); run it on the shardmap transport",
                transport="pallas")
        if chunks == 1 and _cdiv(rows, sub) * sub <= max_rows:
            return rows, rows                  # one block, the whole slot
        steps = max(chunks, _cdiv(rows, max_rows))
        block = _cdiv(_cdiv(rows, steps), sub) * sub
        return steps * block, block

    def _build(self, rows: int, block: int, dtype) -> callable:
        n, s = self.nranks, self.num_slots
        spec = pl.BlockSpec((n, s, block, _LANES), lambda i: (0, 0, i, 0))
        scratch = [pltpu.VMEM((self.inbox_slots, block, _LANES), dtype)]
        if self.ex._post is not None:
            scratch.insert(0, pltpu.VMEM((n, s, block, _LANES), dtype))
        return pl.pallas_call(
            self._kernel,
            grid=(rows // block,),
            in_specs=[spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((n, s, rows, _LANES), dtype),
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=self.interpret,
        )

    def _prepare(self, shape, dtype, chunks: int):
        """Validate a global buffer shape; returns (elems, rows, block,
        jitted kernel)."""
        n, s = self.nranks, self.num_slots
        if tuple(shape[:2]) != (n, s):
            raise ValueError(
                f"PallasExec.run: buffer [{tuple(shape)}] does not match "
                f"[nranks={n}, num_slots={s}, *slot]")
        slot = tuple(shape[2:])
        if chunks < 1:
            raise ValueError(f"PallasExec.run: chunks must be >= 1, "
                             f"got {chunks}")
        if chunks > 1 and (not slot or slot[0] % chunks):
            raise ValueError(
                f"PallasExec.run: slot row axis {slot[:1]} must "
                f"divide by chunks={chunks}")
        dtype = jnp.dtype(dtype)
        elems = max(1, math.prod(slot))
        rows, block = self._plan(elems, dtype.itemsize, chunks)
        key = (rows, block, dtype)
        call = self._jitted.get(key)
        if call is None:
            call = jax.jit(self._build(rows, block, dtype))
            self._jitted[key] = call
        return elems, rows, call

    def lower(self, shape, dtype, *, chunks: int = 1, sharding=None):
        """The kernel launch ``run`` makes for a global buffer of this
        shape, lowered and not run (``.as_text()``, ``.compile()``)."""
        _, rows, call = self._prepare(shape, dtype, chunks)
        return call.lower(jax.ShapeDtypeStruct(
            (self.nranks, self.num_slots, rows, _LANES), dtype,
            sharding=sharding))

    def run(self, gbuf, *, chunks: int = 1):
        """Execute the whole schedule as ONE Pallas kernel launch.

        ``gbuf`` is [nranks, num_slots, *slot] (any array-like; returns
        jnp).  ``chunks > 1`` requires the slot row axis to divide by
        ``chunks`` and runs at least that many grid steps (double-
        buffered block pipeline; bit-identical to ``chunks=1``)."""
        gbuf = jnp.asarray(gbuf)
        n, s = self.nranks, self.num_slots
        elems, rows, call = self._prepare(gbuf.shape, gbuf.dtype, chunks)
        self.launches += 1
        flat = gbuf.reshape(n, s, elems)
        if rows * _LANES != elems:
            flat = jnp.pad(flat, ((0, 0), (0, 0),
                                  (0, rows * _LANES - elems)))
        out = call(flat.reshape(n, s, rows, _LANES))
        out = out.reshape(n, s, rows * _LANES)[:, :, :elems]
        return out.reshape((n, s) + gbuf.shape[2:])


# ---------------------------------------------------------------------------
# process-level cache (persistent-collective init, like executor._CACHE)
# ---------------------------------------------------------------------------


_CACHE: dict[tuple, PallasExec] = {}


def get_pallas_exec(schedule: CommSchedule, *,
                    topo: Topology | None = None,
                    optimize: bool | None = None,
                    interpret: bool | None = None) -> PallasExec:
    """Lower once per (schedule content, optimize, validation flag,
    topology geometry, interpret mode), then reuse forever — the same
    key discipline as ``executor.get_executor`` (whose compiled rounds
    this lowering consumes), plus the interpret flag."""
    ex = get_executor(schedule, optimize=optimize, topo=topo)
    mode = pallas_interpret() if interpret is None else bool(interpret)
    key = (schedule.fingerprint(), ex.optimize,
           validate_schedules_enabled(),
           None if topo is None else topo.fingerprint(), mode)
    pex = _CACHE.get(key)
    if pex is None or pex.ex is not ex:      # executor cache was cleared
        pex = PallasExec(ex, interpret=mode)
        _CACHE[key] = pex
    return pex


def clear_cache() -> None:
    """Drop every lowered Pallas executor (tests; after env flips)."""
    _CACHE.clear()
