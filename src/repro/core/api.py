"""MPIX-style user API (paper Listings 2/4): drop-in collectives with a
publicly selectable ``algorithm=`` argument.

    y = mpix_allreduce(x, ("pod", "data"))                   # default select
    y = mpix_allreduce(x, ("pod", "data"), algorithm="hierarchical")
    y = mpix_allgather(x, "model", algorithm="bruck")
    y = mpix_allreduce(x, "data", policy="tuned")            # empirical table

All functions must be called *inside* ``shard_map`` whose manual axes
include ``axis_names``; ``algorithm="xla"`` routes to the substrate
(XLA's native lowering — the analogue of calling the system MPI), every
other name routes to a persistent ``Schedule`` executed over ``ppermute``.

Schedules are built once per (collective, algorithm, topology) and cached
— MPI Advance's "persistent" initialization-time setup — and execute
through the process-level compiled-executor cache (``core.executor``):
tables baked on device once, rounds fused, one jit trace per (schedule,
shape, dtype).  ``executor_cache_stats()`` / ``clear_executor_cache()``
expose that layer.
"""
from __future__ import annotations

import time
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.topology import (DCN_LINK, ICI_LINK, TopoLevel, Topology)
from repro.core.transport import (PallasTransport, ShardMapTransport,
                                  TransportError, _flat_rank)
from repro.core.schedule import NotApplicable
from repro.core.resilient import (Attempt, DegradationReport,
                                  UnrecoverableError, announce,
                                  resolve_resilience)
from repro.core import chaos as _chaos
from repro.core import selector
from repro.core.algorithms import REGISTRY


def _axes_tuple(axis_names) -> tuple[str, ...]:
    return (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)


def topology_from_axes(axis_names: Sequence[str]) -> Topology:
    """Topology for the flat rank space of ``axis_names`` (row-major).

    Convention: if the first axis is named ``"pod"`` it is the DCN axis and
    everything after it is intra-pod; otherwise the whole space is one pod.
    A single intra-pod axis canonicalizes to the historical 1/2-level
    form (stable fingerprints for every existing call site); two or more
    intra-pod axes are kept as distinct ICI levels, giving the tuner
    per-axis-geometry (torus-aware) fingerprints.
    Must be called inside shard_map (uses static axis sizes).
    """
    names = _axes_tuple(axis_names)
    sizes = [jax.lax.axis_size(n) for n in names]
    nranks = 1
    for s in sizes:
        nranks *= s
    has_pod = names[0] == "pod" and len(names) > 1
    intra = list(zip(names, sizes))[1:] if has_pod else list(
        zip(names, sizes))
    if len(intra) <= 1:
        return Topology(nranks=nranks,
                        ranks_per_pod=nranks // sizes[0] if has_pod
                        else nranks)
    levels = []
    if has_pod:
        levels.append(TopoLevel("dcn", sizes[0], DCN_LINK, dcn=True))
    levels += [TopoLevel(nm, sz, ICI_LINK) for nm, sz in intra]
    return Topology.from_levels(levels)


# plan cache: (collective, algorithm, topo) -> CommSchedule.  A plain
# dict rather than lru_cache so drift healing / elastic swaps can evict
# by topology (``invalidate_topology``) instead of all-or-nothing.
_SCHEDULES: dict = {}


def _schedule(collective: str, algorithm: str, topo: Topology):
    key = (collective, algorithm, topo)
    sched = _SCHEDULES.get(key)
    if sched is None:
        sched = REGISTRY[collective][algorithm](topo)
        # warm the persistent-executor cache at plan time (MPI-4
        # persistent init): by the first traced call the tables are
        # already baked and the topology-armed fusion/reordering pass
        # has run
        from repro.core import executor
        executor.get_executor(sched, topo=topo)
        _SCHEDULES[key] = sched
    return sched


def invalidate_topology(topo: Topology | str) -> dict:
    """Scoped cache eviction for one geometry (drift heal / elastic
    swap): drop the cached plans built against ``topo`` (a ``Topology``
    or its fingerprint string) and the compiled executors armed with
    its fingerprint.  Plans and executors for every other geometry —
    including the new measured one about to take over — are untouched.
    Returns ``{"plans": n, "executors": m}`` eviction counts.
    """
    from repro.core import executor
    fp = topo if isinstance(topo, str) else topo.fingerprint()
    doomed = [k for k in _SCHEDULES if k[2].fingerprint() == fp]
    for k in doomed:
        del _SCHEDULES[k]
    return {"plans": len(doomed),
            "executors": executor.invalidate_topology(fp)}


def executor_cache_stats() -> dict:
    """Compiled-executor cache telemetry: size, hit/miss counts, and per
    executor (rounds before/after fusion, trace/sim-run counters)."""
    from repro.core import executor
    return executor.cache_stats()


def clear_executor_cache() -> None:
    """Drop every compiled executor (tests; after env-flag flips)."""
    from repro.core import executor
    executor.clear_cache()


# Selection policy used when algorithm="auto" and no per-call ``policy=``
# is given: "fixed" (paper defaults), "model" (alpha-beta argmin) or
# "tuned" (persisted empirical table; see repro.core.tuner).
_DEFAULT_POLICY = "model"


def set_default_policy(policy: str) -> None:
    """Set the process-wide selection policy for algorithm="auto"."""
    if policy not in selector.POLICIES:
        raise ValueError(f"unknown selection policy {policy!r}; "
                         f"expected one of {selector.POLICIES}")
    global _DEFAULT_POLICY
    _DEFAULT_POLICY = policy


def get_default_policy() -> str:
    return _DEFAULT_POLICY


def ensure_tuned(topo: Topology, *, path=None, heal: bool = True,
                 set_policy: bool = True, **tune_kwargs):
    """Init-time entry for ``policy="tuned"`` (persistent-MPI style).

    Loads (tuning once if missing) the empirical table for ``topo``'s
    substrate; with ``heal=True`` any performance-guideline violation in
    a cached table triggers a scoped re-measure of only the offending
    (collective, size-bucket) cells and persists a bumped generation —
    see ``tuner.ensure_table``.  With ``set_policy=True`` the process
    default policy flips to "tuned", so every later ``algorithm="auto"``
    collective resolves from the (healed) table.  Returns the table.
    """
    from repro.core import tuner  # local: avoid import cycle
    table = tuner.ensure_table(topo, path=path, heal=heal, **tune_kwargs)
    if set_policy:
        set_default_policy("tuned")
    return table


def _resolve(collective: str, algorithm: str, topo: Topology, nbytes: int,
             policy: str | None = None):
    if algorithm == "auto":
        algorithm = selector.select(collective, topo, nbytes,
                                    policy=policy or _DEFAULT_POLICY)
    if algorithm == "xla":
        return "xla", None
    return algorithm, _schedule(collective, algorithm, topo)


# Transport substrates selectable per call: "shardmap" (one ppermute per
# compiled round), "pallas" (whole schedule as one device-side kernel;
# see core.pallas_lowering), or "auto" (the tuner's ``transport`` policy
# cell prices the two per size bucket).
TRANSPORTS = ("shardmap", "pallas", "auto")


def _check_transport(transport: str) -> None:
    """Name check only — callable before any axis/topology resolution,
    so a typo'd transport fails loudly even outside shard_map."""
    if transport not in TRANSPORTS:
        raise ValueError(f"unknown transport {transport!r}; "
                         f"expected one of {TRANSPORTS}")


def _resolve_transport(transport: str, topo: Topology, nbytes: int,
                       policy: str | None = None, schedule=None) -> str:
    """Validate + resolve a transport name to a concrete substrate for
    ``schedule`` (None: no schedule runs on it)."""
    _check_transport(transport)
    if transport == "auto":
        from repro.core import tuner  # local: avoid import cycle
        transport = tuner.select_transport(
            topo, nbytes, policy=policy or _DEFAULT_POLICY,
            schedule=schedule)
    return transport


# Process-wide chaos plan (``core.chaos.FaultPlan``): when set, every
# transport the api constructs is wrapped so seeded faults fire on the
# real mpix_* execution paths.  Test/CI-only; None in production.
_CHAOS_PLAN = None


def set_chaos(plan) -> None:
    """Install (or clear, with None) the process-wide fault plan; all
    subsequently constructed mpix_* transports are chaos-wrapped."""
    global _CHAOS_PLAN
    _CHAOS_PLAN = plan


def get_chaos():
    return _CHAOS_PLAN


def _transport_instance(kind: str, topo: Topology, names):
    cls = PallasTransport if kind == "pallas" else ShardMapTransport
    return _chaos.wrap(cls(topo.nranks, names, topo=topo), _CHAOS_PLAN)


# Degradation telemetry: every mpix_* call that needed the recovery
# ladder appends its DegradationReport here; ``FaultTolerantLoop``
# drains the list each step so a degraded mesh is *visible*, not silent.
_DEGRADATIONS: list = []


def last_degradation():
    """The most recent DegradationReport (None when nothing degraded)."""
    return _DEGRADATIONS[-1] if _DEGRADATIONS else None


def take_degradations() -> list:
    """Drain and return all accumulated DegradationReports."""
    out = list(_DEGRADATIONS)
    _DEGRADATIONS.clear()
    return out


def _attempt(collective: str, run, kind: str, algo: str):
    """``run(kind, algo)`` under the named scope that says what it
    executes: ``mpix.<collective>.<algorithm>.<transport>``, or
    ``mpix.<collective>.xla`` for the native lowering."""
    scope = (f"mpix.{collective}.xla" if algo == "xla"
             else f"mpix.{collective}.{algo}.{kind}")
    with jax.named_scope(scope):
        return run(kind, algo)


def _execute(collective: str, run, *, algorithm: str, policy,
             topo: Topology, nbytes: int, transport: str, resilience,
             xla_ok: bool = True, schedule=None):
    """Shared execution path of every mpix_* collective.

    ``run(kind, algo)`` closes over the collective's buffers and does
    one full attempt on transport ``kind`` ("shardmap"/"pallas", or
    "xla" when ``algo == "xla"``).  Without ``resilience`` this is a
    zero-overhead passthrough (today's behavior).  With it, the TRACE-
    TIME recovery ladder runs: detected faults — a raised
    ``TransportError`` (failed launch, injected chaos failure), an
    ``NotApplicable`` refit miss, or a wall-clock deadline overrun (an
    injected hang burns host time during tracing) — are retried with
    exponential backoff, degraded to the other ppermute/pallas
    substrate, refitted down the selector's algorithm ladder, and
    finally routed to the substrate's native lowering
    (``algorithm="xla"``, the system-MPI analogue) before a typed
    ``UnrecoverableError`` is raised.  ``schedule`` is what ``run``
    executes, for collectives outside ``REGISTRY``.

    Honest taxonomy: values here are *traced*, so data-dependent
    verification (canary/checksum) is impossible at this layer —
    silent corruption is caught by the host-level ``ResilientExec``
    (core.resilient), which the chaos registry sweep drives over all
    three transports.  This layer recovers every *detected* fault.
    """
    _check_transport(transport)
    if algorithm == "auto":
        algorithm = selector.select(collective, topo, nbytes,
                                    policy=policy or _DEFAULT_POLICY)
    opts = resolve_resilience(resilience)
    if algorithm == "xla":
        return _attempt(collective, run, "xla", "xla")
    if transport == "auto" and schedule is None:
        schedule = _schedule(collective, algorithm, topo)
    kind = _resolve_transport(transport, topo, nbytes, policy, schedule)
    if opts is None:
        return _attempt(collective, run, kind, algorithm)

    report = DegradationReport(schedule=f"{collective}.{algorithm}",
                               verify="off")

    def finish(out, rung):
        report.recovered_with = rung
        if report.degraded:
            announce(report)
            _DEGRADATIONS.append(report)
        return out

    kinds = [kind] + [k for k in ("shardmap", "pallas") if k != kind]
    for k in kinds:
        delay = opts.backoff_s
        for attempt in range(opts.max_retries + 1):
            t0 = time.perf_counter()
            try:
                out = _attempt(collective, run, k, algorithm)
            except TransportError as e:
                report.attempts.append(Attempt(
                    rung=k, algorithm=algorithm, attempt=attempt,
                    outcome="fault", detail=str(e),
                    seconds=time.perf_counter() - t0))
                time.sleep(delay)
                delay *= opts.backoff_mult
                continue
            dt = time.perf_counter() - t0
            if opts.deadline_s is not None and dt > opts.deadline_s:
                report.attempts.append(Attempt(
                    rung=k, algorithm=algorithm, attempt=attempt,
                    outcome="timeout", seconds=dt,
                    detail=f"{dt:.4f}s > deadline {opts.deadline_s:.4f}s"))
                time.sleep(delay)
                delay *= opts.backoff_mult
                continue
            report.attempts.append(Attempt(
                rung=k, algorithm=algorithm, attempt=attempt,
                outcome="ok", seconds=dt))
            return finish(out, k)
    if opts.refit:
        ladder = [a for a in selector._FIXED.get(collective, ())
                  if a != algorithm]
        ladder += [a for a in REGISTRY.get(collective, {})
                   if a != algorithm and a not in ladder]
        for cand in ladder:
            try:
                out = _attempt(collective, run, kinds[0], cand)
            except (TransportError, NotApplicable) as e:
                report.attempts.append(Attempt(
                    rung="refit", algorithm=cand, attempt=0,
                    outcome="fault" if isinstance(e, TransportError)
                    else "skipped", detail=str(e) or type(e).__name__))
                continue
            report.attempts.append(Attempt(
                rung="refit", algorithm=cand, attempt=0, outcome="ok"))
            report.refit_algorithm = cand
            return finish(out, kinds[0])
    if xla_ok:
        try:
            out = _attempt(collective, run, "xla", "xla")
        except Exception as e:  # native lowering is best-effort terminal
            report.attempts.append(Attempt(
                rung="xla", algorithm="xla", attempt=0,
                outcome="fault", detail=str(e)))
        else:
            report.attempts.append(Attempt(
                rung="xla", algorithm="xla", attempt=0, outcome="ok"))
            report.refit_algorithm = "xla"
            return finish(out, "xla")
    raise UnrecoverableError(
        f"{collective} could not be recovered on any transport or "
        f"algorithm", report)


def _pad_lead(x: jax.Array, mult: int):
    """``x`` with zero rows appended to its leading dim up to a multiple
    of ``mult``."""
    rem = (-x.shape[0]) % mult
    if rem:
        x = jnp.pad(x, ((0, rem),) + ((0, 0),) * (x.ndim - 1))
    return x


# ---------------------------------------------------------------------------


def mpix_allgather(x: jax.Array, axis_names, *, algorithm: str = "auto",
                   policy: str | None = None,
                   topo: Topology | None = None,
                   transport: str = "shardmap",
                   resilience=None) -> jax.Array:
    """Tiled allgather of the local shard along its leading dim."""
    names = _axes_tuple(axis_names)
    _check_transport(transport)
    topo = topo or topology_from_axes(names)
    nbytes = x.size * x.dtype.itemsize
    n = topo.nranks

    def run(kind, algo):
        if algo == "xla":
            return jax.lax.all_gather(x, names, tiled=True)
        sched = _schedule("allgather", algo, topo)
        tr = _transport_instance(kind, topo, names)
        buf = jnp.zeros((n,) + x.shape, x.dtype)
        buf = buf.at[_flat_rank(names)].set(x)
        out = tr.run(sched, buf)
        return out.reshape((n * x.shape[0],) + x.shape[1:])

    return _execute("allgather", run, algorithm=algorithm, policy=policy,
                    topo=topo, nbytes=nbytes, transport=transport,
                    resilience=resilience)


def mpix_allreduce(x: jax.Array, axis_names, *, algorithm: str = "auto",
                   policy: str | None = None,
                   topo: Topology | None = None,
                   transport: str = "shardmap",
                   resilience=None) -> jax.Array:
    names = _axes_tuple(axis_names)
    _check_transport(transport)
    topo = topo or topology_from_axes(names)
    nbytes = x.size * x.dtype.itemsize
    n = topo.nranks

    def run(kind, algo):
        if algo == "xla":
            return jax.lax.psum(x, names)
        sched = _schedule("allreduce", algo, topo)
        tr = _transport_instance(kind, topo, names)
        # n chunks cut along the leading dim, zero padded up to a
        # multiple of n: no relayout of the minor dims
        xs = x.reshape(1) if x.ndim == 0 else x
        lead = xs.shape[0]
        xs = _pad_lead(xs, n)
        out = tr.run(sched, xs.reshape((n, -1) + xs.shape[1:]))
        return out.reshape(xs.shape)[:lead].reshape(x.shape)

    return _execute("allreduce", run, algorithm=algorithm, policy=policy,
                    topo=topo, nbytes=nbytes, transport=transport,
                    resilience=resilience)


def mpix_reduce_scatter(x: jax.Array, axis_names, *,
                        algorithm: str = "auto",
                        policy: str | None = None,
                        topo: Topology | None = None,
                        transport: str = "shardmap",
                        resilience=None) -> jax.Array:
    """Reduce along axes; scatter over the leading dim (must divide)."""
    names = _axes_tuple(axis_names)
    _check_transport(transport)
    topo = topo or topology_from_axes(names)
    nbytes = x.size * x.dtype.itemsize
    n = topo.nranks
    if x.shape[0] % n:
        raise ValueError(
            f"mpix_reduce_scatter: leading dim {x.shape[0]} of input "
            f"shape {tuple(x.shape)} must be divisible by nranks={n} "
            f"(one scatter block per rank)")

    def run(kind, algo):
        if algo == "xla":
            return jax.lax.psum_scatter(x, names, scatter_dimension=0,
                                        tiled=True)
        sched = _schedule("reduce_scatter", algo, topo)
        tr = _transport_instance(kind, topo, names)
        blocks = x.reshape((n, x.shape[0] // n) + x.shape[1:])
        out = tr.run(sched, blocks)
        return out[_flat_rank(names)]

    return _execute("reduce_scatter", run, algorithm=algorithm,
                    policy=policy, topo=topo, nbytes=nbytes,
                    transport=transport, resilience=resilience)


def mpix_alltoall(x: jax.Array, axis_names, *, algorithm: str = "auto",
                  policy: str | None = None,
                  topo: Topology | None = None,
                  transport: str = "shardmap",
                  resilience=None) -> jax.Array:
    """Alltoall over the leading dim: in block d = data for rank d;
    out block s = data from rank s.  Leading dim must divide by nranks."""
    names = _axes_tuple(axis_names)
    _check_transport(transport)
    topo = topo or topology_from_axes(names)
    nbytes = x.size * x.dtype.itemsize
    n = topo.nranks
    if x.shape[0] % n:
        raise ValueError(
            f"mpix_alltoall: leading dim {x.shape[0]} of input shape "
            f"{tuple(x.shape)} must be divisible by nranks={n} "
            f"(one block per destination rank)")

    def run(kind, algo):
        if algo == "xla":
            # tiled alltoall: leading dim split into n segments; segment
            # s of the output came from rank s.
            return jax.lax.all_to_all(x, names, split_axis=0,
                                      concat_axis=0, tiled=True)
        sched = _schedule("alltoall", algo, topo)
        tr = _transport_instance(kind, topo, names)
        blocks = x.reshape((n, x.shape[0] // n) + x.shape[1:])
        if sched.num_blocks > n:  # schedules with a separate recv region
            pad = jnp.zeros((sched.num_blocks - n,) + blocks.shape[1:],
                            x.dtype)
            blocks = jnp.concatenate([blocks, pad], axis=0)
        out = tr.run(sched, blocks)
        return out[: sched.result_blocks].reshape(x.shape)

    return _execute("alltoall", run, algorithm=algorithm, policy=policy,
                    topo=topo, nbytes=nbytes, transport=transport,
                    resilience=resilience)


def mpix_alltoall_overlap(x: jax.Array, axis_names, consume, init, *,
                          chunks: int = 0, compute_s: float = 0.0,
                          algorithm: str = "auto",
                          policy: str | None = None,
                          topo: Topology | None = None,
                          transport: str = "shardmap",
                          resilience=None):
    """Partitioned (pipelined) alltoall: the exchange runs in row
    chunks and each chunk's output is folded through
    ``consume(carry, out_chunk, i) -> carry`` as soon as it lands, so
    chunk ``i+1``'s transfer overlaps chunk ``i``'s consumer compute
    (MPIPCL early-bird receive on the MoE dispatch path).

    ``out_chunk`` is the alltoall of the matching row slice of every
    block: shape [(n * rows/chunks), ...] with the usual alltoall block
    order.  ``chunks=0`` lets the tuner pick (``select_overlap_chunks``
    prices the software pipeline against ``compute_s`` seconds of
    consumer compute; policy "tuned" reads the persisted table);
    ``chunks=1`` degenerates to one ``mpix_alltoall`` + one ``consume``
    call — always a legal fallback.  Explicit ``chunks>1`` must divide
    the per-block row count."""
    names = _axes_tuple(axis_names)
    _check_transport(transport)
    topo = topo or topology_from_axes(names)
    nbytes = x.size * x.dtype.itemsize
    n = topo.nranks
    if x.shape[0] % n:
        raise ValueError(
            f"mpix_alltoall_overlap: leading dim {x.shape[0]} of input "
            f"shape {tuple(x.shape)} must be divisible by nranks={n} "
            f"(one block per destination rank)")
    if chunks < 0:
        raise ValueError(
            f"mpix_alltoall_overlap: chunks must be >= 0, got {chunks}")
    rows = x.shape[0] // n
    if chunks == 0:
        from repro.core import tuner  # local: avoid import cycle
        chunks = tuner.select_overlap_chunks(
            topo, x.size * x.dtype.itemsize, compute_s,
            policy=policy or _DEFAULT_POLICY)
        while rows % chunks:          # auto-picked: clamp to a divisor
            chunks -= 1
    elif chunks > 1 and rows % chunks:
        raise ValueError(
            f"mpix_alltoall_overlap: per-block row count {rows} must "
            f"be divisible by chunks={chunks}")
    if chunks <= 1:
        return consume(init, mpix_alltoall(x, names, algorithm=algorithm,
                                           policy=policy, topo=topo,
                                           transport=transport,
                                           resilience=resilience), 0)
    rc = rows // chunks
    nchunks = chunks

    def run(kind, algo):
        if algo == "xla":
            blocks = x.reshape((n, nchunks, rc) + x.shape[1:])

            def body(carry, xi):
                xc, i = xi
                out = jax.lax.all_to_all(
                    xc.reshape((n * rc,) + x.shape[1:]), names,
                    split_axis=0, concat_axis=0, tiled=True)
                return consume(carry, out, i), None

            carry, _ = jax.lax.scan(
                body, init, (blocks.swapaxes(0, 1),
                             jnp.arange(nchunks, dtype=jnp.int32)))
            return carry
        sched = _schedule("alltoall", algo, topo)
        tr = _transport_instance(kind, topo, names)
        blocks = x.reshape((n, rows) + x.shape[1:])
        if sched.num_blocks > n:  # schedules with a separate recv region
            pad = jnp.zeros((sched.num_blocks - n,) + blocks.shape[1:],
                            x.dtype)
            blocks = jnp.concatenate([blocks, pad], axis=0)

        def fold(carry, out_c, i):
            out = (out_c[: sched.result_blocks]
                   .reshape((n * rc,) + x.shape[1:]))
            return consume(carry, out, i)

        return tr.run_chunked(sched, blocks, chunks=nchunks, consume=fold,
                              init=init)

    return _execute("alltoall", run, algorithm=algorithm, policy=policy,
                    topo=topo, nbytes=nbytes, transport=transport,
                    resilience=resilience)


# ---------------------------------------------------------------------------
# neighborhood collectives (paper §2.2, Listing 3/4)
# ---------------------------------------------------------------------------


def make_neighbor_plan(graph, topo: Topology, *,
                       aggregate: bool | None = None,
                       policy: str | None = None,
                       elem_bytes: int | None = None):
    """Compile a persistent neighborhood-alltoallv plan (init-time, not
    traced).  ``aggregate=None`` resolves standard-vs-locality-aware via
    the selection policy ladder (process default when ``policy=None``;
    "tuned" reads the winner persisted by ``tuner.autotune``).
    ``elem_bytes`` is the byte width of one value row (feat * itemsize)
    — it anchors the model comparison and the tuned-table lookup, so
    pass it whenever rows are wider than one float32."""
    from repro.core.plan import ELEM_BYTES, build_plan
    return build_plan(graph, topo, aggregate=aggregate,
                      policy=policy or _DEFAULT_POLICY,
                      elem_bytes=ELEM_BYTES if elem_bytes is None
                      else elem_bytes)


def mpix_neighbor_alltoallv(x: jax.Array, axis_names, plan, *,
                            transport: str = "shardmap",
                            resilience=None) -> jax.Array:
    """Execute a compiled ``NeighborPlan`` (call inside shard_map).

    ``x`` is this rank's [n_local_max, feat] value rows; returns
    [n_recv_max, feat] (rows past this rank's recv size are zeros)."""
    from repro.core.plan import run_shardmap
    names = _axes_tuple(axis_names)
    nbytes = x.size * x.dtype.itemsize

    def run(kind, algo):
        return run_shardmap(plan, x, names, transport=kind)

    return _execute("neighbor_alltoallv", run, algorithm=plan.name,
                    policy=None, topo=plan.topo, nbytes=nbytes,
                    transport=transport, resilience=resilience,
                    xla_ok=False, schedule=plan.schedule)


# ---------------------------------------------------------------------------
# compute-fused terminal rounds
# ---------------------------------------------------------------------------


def mpix_allreduce_rmsnorm(x: jax.Array, axis_names, scale: jax.Array, *,
                           eps: float = 1e-6, gemma_style: bool = False,
                           algorithm: str = "auto",
                           policy: str | None = None,
                           topo: Topology | None = None,
                           transport: str = "pallas",
                           resilience=None) -> jax.Array:
    """Allreduce ``x`` over ``axis_names``, then rmsnorm the result —
    with the reduction's terminal round fused INTO the rmsnorm kernel.

    On the pallas transport the partial activations are combined with a
    single ``all_gather`` and the summation happens inside the rmsnorm
    Pallas kernel itself (``kernels.rmsnorm.rmsnorm_allreduce``): the
    reduced tensor is never materialized in HBM, saving one full
    write+read round trip vs allreduce-then-normalize (the modeled win
    gated in BENCH_transport.json).  ``x`` is [..., d] with rmsnorm over
    the last dim; summation is in f32 regardless of dtype, so results
    match psum+rmsnorm to float tolerance (NOT bit-exact — the add
    order differs from a ring reduction's).  On "shardmap" it falls
    back to ``mpix_allreduce`` followed by the plain kernel."""
    names = _axes_tuple(axis_names)
    _check_transport(transport)
    topo = topo or topology_from_axes(names)
    from repro.kernels.rmsnorm import ops as rms_ops
    kind = _resolve_transport(transport, topo, x.size * x.dtype.itemsize,
                              policy)
    if kind == "pallas":
        try:
            parts = jax.lax.all_gather(
                x, names if len(names) > 1 else names[0])
            parts = parts.reshape((topo.nranks,) + x.shape)
            return rms_ops.rmsnorm_allreduce(parts, scale, eps,
                                             gemma_style)
        except TransportError as e:
            if resolve_resilience(resilience) is None:
                raise
            # degrade the fused kernel to allreduce-then-normalize
            # (resilient itself) and surface the decision
            report = DegradationReport(
                schedule="allreduce_rmsnorm.fused", verify="off")
            report.attempts.append(Attempt(
                rung="pallas", algorithm="fused", attempt=0,
                outcome="fault", detail=str(e)))
            report.recovered_with = "shardmap"
            announce(report)
            _DEGRADATIONS.append(report)
    y = mpix_allreduce(x, names, algorithm=algorithm, policy=policy,
                       topo=topo, resilience=resilience)
    return rms_ops.rmsnorm(y, scale, eps, gemma_style)


__all__ = [
    "mpix_allgather", "mpix_allreduce", "mpix_reduce_scatter",
    "mpix_alltoall", "mpix_alltoall_overlap", "mpix_allreduce_rmsnorm",
    "mpix_neighbor_alltoallv", "make_neighbor_plan",
    "topology_from_axes", "set_default_policy", "get_default_policy",
    "ensure_tuned", "executor_cache_stats", "clear_executor_cache",
    "invalidate_topology", "TRANSPORTS",
    "set_chaos", "get_chaos", "last_degradation", "take_degradations",
    "UnrecoverableError", "DegradationReport",
]
