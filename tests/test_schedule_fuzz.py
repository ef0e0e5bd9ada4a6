"""Randomized schedule-conformance fuzzing (core.executor's contract).

A generator of random *legal* ``CommSchedule``s — random geometry,
gather/perm/scatter tables, optional reduce and ragged-payload rounds,
optional bijective local pre/post permutations — paired with random
1–4-level topologies, drives three metamorphic properties:

  * **bit-exactness** — the compiled executor (unoptimized, topology-
    free fused, and topology-armed) is bit-identical to the historical
    rank-by-rank oracle ``SimTransport.run_reference`` on every fuzzed
    schedule;
  * **cost safety** — fusion/reordering never raises the alpha-beta
    ``modeled_time``: armed <= topology-free <= original, at small
    (alpha-dominated), medium, and large (beta-dominated) slot sizes;
  * **identity** — ``CommSchedule.fingerprint()`` round-trips: a
    schedule rebuilt from copies of the same tables shares the
    fingerprint, a renamed schedule shares it, any table mutation
    changes it (the executor-cache key is exactly content identity).

The suite runs under the real Hypothesis runner when the ``dev`` extra
is installed and falls back to the seeded stub otherwise, so it is
tier-1 in every environment.  Setting ``REPRO_FUZZ_DETERMINISTIC=1``
(the CI fuzz leg) pins Hypothesis to its derandomized profile so CI
failures reproduce locally from the recorded falsifying example.
"""
import os

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    if os.environ.get("REPRO_FUZZ_DETERMINISTIC"):
        settings.register_profile("repro-fuzz", derandomize=True,
                                  deadline=None)
        settings.load_profile("repro-fuzz")
except ImportError:      # dev extra not installed: seeded fallback
    from _hypothesis_stub import given, settings, st

import dataclasses
import math

from repro.core import executor, pallas_lowering
from repro.core.schedule import CommRound, CommSchedule, ComputeEvent
from repro.core.topology import Topology, flat_topology, torus_topology
from repro.core.transport import SimTransport


@pytest.fixture(autouse=True)
def _fresh_executor_cache():
    executor.clear_cache()
    pallas_lowering.clear_cache()
    yield
    executor.clear_cache()
    pallas_lowering.clear_cache()


# ---------------------------------------------------------------------------
# generators (plain numpy RNG so the hypothesis stub drives them too)
# ---------------------------------------------------------------------------


def rand_topology(rng) -> Topology:
    """Random 1–4-level hierarchy, nranks capped so the rank-by-rank
    oracle stays fast (degenerate size-1 axes included on purpose)."""
    n_axes = int(rng.integers(0, 4))
    sizes = [int(rng.integers(1, 4)) for _ in range(n_axes)]
    npods = int(rng.integers(1, 4))
    while sizes and npods * math.prod(sizes) > 24:
        sizes.pop()
    if not sizes:
        n = max(2, npods * int(rng.integers(1, 9)))
        return (flat_topology(n) if npods == 1
                else Topology(n, n // npods))
    return torus_topology(npods, *sizes)


def rand_round(rng, n: int, slots: int, *, allow_reduce=True) -> CommRound:
    """One random legal round: a random partial matching ((r, r)
    self-pairs included), random gather rows with -1 zero-send padding,
    distinct live scatter targets with -1 dropped-on-arrival holes, an
    optional reduce flag and an optional ragged ``payload``."""
    m = int(rng.integers(1, n + 1))
    srcs = rng.permutation(n)[:m]
    dsts = rng.permutation(n)[:m]
    k = int(rng.integers(1, min(4, slots) + 1))
    gi = np.full((n, k), -1, np.int64)
    si = np.full((n, k), -1, np.int64)
    reduce = bool(allow_reduce and rng.random() < 0.25)
    payload = (np.zeros(n, np.int64)
               if (not reduce and rng.random() < 0.4) else None)
    perm = []
    for s, d in zip(srcs, dsts):
        w = int(rng.integers(1, k + 1))
        g = rng.integers(0, slots, k).astype(np.int64)
        g[w:] = -1
        g[rng.random(k) < 0.15] = -1          # zero-send holes
        t = np.full(k, -1, np.int64)
        t[:w] = rng.permutation(slots)[:w]    # distinct live targets
        t[:w][rng.random(w) < 0.2] = -1       # dropped-on-arrival holes
        gi[s], si[d] = g, t
        perm.append((int(s), int(d)))
        if payload is not None:
            payload[s] = int(rng.integers(0, int((g >= 0).sum()) + 1))
    return CommRound(perm=tuple(perm), gather_idx=gi, scatter_idx=si,
                     reduce=reduce, payload=payload)


def rand_schedule(rng, n: int) -> CommSchedule:
    slots = int(rng.integers(2, 9))
    nrounds = int(rng.integers(1, 6))
    rounds = tuple(rand_round(rng, n, slots) for _ in range(nrounds))
    local_pre = (np.stack([rng.permutation(slots) for _ in range(n)])
                 if rng.random() < 0.3 else None)
    local_post = (np.stack([rng.permutation(slots) for _ in range(n)])
                  if rng.random() < 0.3 else None)
    return CommSchedule(nranks=n, num_slots=slots, rounds=rounds,
                        name="fuzz", local_pre=local_pre,
                        local_post=local_post)


def rand_events(rng, nrounds: int) -> tuple:
    """0–3 random compute events: anchors span the whole schedule
    (``-1`` = after the last round), seconds span alpha-to-beta
    magnitudes, and ~half are splittable so the tail-split move fires
    when legality lines up."""
    if rng.random() < 0.5:
        return ()
    out = []
    for i in range(int(rng.integers(1, 4))):
        anchor = -1 if rng.random() < 0.5 else int(
            rng.integers(0, nrounds))
        out.append(ComputeEvent(
            f"ev{i}", float(10.0 ** rng.uniform(-7, -2)),
            after_round=anchor,
            splittable=bool(rng.random() < 0.5),
            parts=int(rng.choice([0, 2, 4]))))
    return tuple(out)


# ---------------------------------------------------------------------------
# the metamorphic core
# ---------------------------------------------------------------------------


_PROBE_SLOT_BYTES = (1, 4096, 1 << 20)   # alpha-, mixed-, beta-dominated


def check_conformance(sched: CommSchedule, topo: Topology, rng) -> None:
    n = sched.nranks
    tr = SimTransport(n)
    buf = rng.integers(-8, 8, (n, sched.num_slots, 2)).astype(np.float32)
    want = tr.run_reference(sched, buf)
    armed = executor.compile_schedule(sched, optimize=True, topo=topo)
    free = executor.compile_schedule(sched, optimize=True)
    plain = executor.compile_schedule(sched, optimize=False)
    # bit-exactness of every compile mode vs the rank-by-rank oracle
    assert np.array_equal(want, armed.run_sim(buf)), sched.name
    assert np.array_equal(want, free.run_sim(buf))
    assert np.array_equal(want, plain.run_sim(buf))
    # cost safety at every probe size: armed <= topology-free <= original
    ev_s = sum(e.seconds for e in sched.compute_events)
    for s in _PROBE_SLOT_BYTES:
        t_orig = sched.modeled_time(topo, s)
        t_free = free.compiled_schedule.modeled_time(topo, s)
        t_armed = armed.compiled_schedule.modeled_time(topo, s)
        tol = 1 + 1e-9
        assert t_free <= t_orig * tol, (s, t_free, t_orig)
        assert t_armed <= t_free * tol, (s, t_armed, t_free)
        assert t_armed <= t_orig * tol, (s, t_armed, t_orig)
        # pipelined pass 3: any packing (split or not) never prices
        # above the armed serial chain plus the registered compute
        assert armed.makespan(s) <= (t_armed + ev_s) * tol, (
            s, armed.makespan(s), t_armed, ev_s)
    # a committed tail split must stay an execution no-op (bit-exact)
    if armed.pipelined_schedule is not None:
        assert armed.pipeline_tail_parts >= 2
        assert np.array_equal(
            want, tr.run_reference(armed.pipelined_schedule, buf))


def check_fingerprint_roundtrip(sched: CommSchedule) -> None:
    rebuilt = CommSchedule(
        nranks=sched.nranks, num_slots=sched.num_slots,
        rounds=tuple(CommRound(perm=r.perm,
                               gather_idx=r.gather_idx.copy(),
                               scatter_idx=r.scatter_idx.copy(),
                               reduce=r.reduce,
                               payload=None if r.payload is None
                               else r.payload.copy())
                     for r in sched.rounds),
        name="rebuilt-under-another-name",
        slot_bytes=sched.slot_bytes,
        local_pre=None if sched.local_pre is None
        else np.asarray(sched.local_pre).copy(),
        local_post=None if sched.local_post is None
        else np.asarray(sched.local_post).copy(),
        out_slots=sched.out_slots, out_offsets=sched.out_offsets)
    assert rebuilt.fingerprint() == sched.fingerprint()
    # any table mutation must change the identity
    rnd = sched.rounds[0]
    g = rnd.gather_idx.copy()
    g[0, 0] = (g[0, 0] + 1) % sched.num_slots   # legal, and always moves
    mutated = dataclasses.replace(
        sched,
        rounds=(dataclasses.replace(rnd, gather_idx=g),) + sched.rounds[1:])
    assert mutated.fingerprint() != sched.fingerprint()


# ---------------------------------------------------------------------------
# properties
# ---------------------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_fuzzed_schedules_conform(seed):
    """Random schedule x random 1–4-level topology: compiled execution
    is bit-exact and fusion/reordering never raises modeled time."""
    rng = np.random.default_rng(seed)
    topo = rand_topology(rng)
    sched = rand_schedule(rng, topo.nranks)
    check_conformance(sched, topo, rng)


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_fuzzed_event_schedules_makespan_safe(seed):
    """Random schedule + random compute events: the makespan chain
    (packed <= armed serial + compute, pointwise) and tail-split
    bit-exactness hold under fuzzing, and attaching events never
    perturbs execution (they are model-only)."""
    rng = np.random.default_rng(seed)
    topo = rand_topology(rng)
    base = rand_schedule(rng, topo.nranks)
    sched = dataclasses.replace(
        base, compute_events=rand_events(rng, len(base.rounds)))
    check_conformance(sched, topo, rng)
    if sched.compute_events:
        # events change identity (cache key) but not results
        assert sched.fingerprint() != base.fingerprint()
        buf = rng.integers(-8, 8, (topo.nranks, sched.num_slots, 2)
                           ).astype(np.float32)
        a = executor.compile_schedule(sched, optimize=True, topo=topo)
        b = executor.compile_schedule(base, optimize=True, topo=topo)
        assert np.array_equal(a.run_sim(buf), b.run_sim(buf))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_fuzzed_fingerprints_roundtrip(seed):
    rng = np.random.default_rng(seed)
    topo = rand_topology(rng)
    check_fingerprint_roundtrip(rand_schedule(rng, topo.nranks))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), probe=st.sampled_from([0, 1, 2]))
def test_fuzzed_reduce_only_schedules_pass_through(seed, probe):
    """Reduce rounds are barriers for every compile mode: a schedule of
    only reduce rounds keeps its round count under the armed pass and
    stays bit-exact (accumulation order is bit-exactness-critical)."""
    rng = np.random.default_rng(seed)
    topo = rand_topology(rng)
    n = topo.nranks
    slots = int(rng.integers(2, 7))
    rounds = []
    for _ in range(int(rng.integers(1, 4))):
        rnd = rand_round(rng, n, slots, allow_reduce=False)
        rounds.append(dataclasses.replace(rnd, reduce=True, payload=None))
    sched = CommSchedule(nranks=n, num_slots=slots, rounds=tuple(rounds),
                         name="fuzz.reduce")
    ex = executor.compile_schedule(sched, optimize=True, topo=topo)
    # a round survives compilation iff some edge delivers something;
    # reduce rounds are never merged or reordered away
    live = sum(1 for r in rounds
               if any((r.scatter_idx[d] >= 0).any() for _, d in r.perm))
    assert ex.rounds_after == live
    buf = rng.integers(-4, 4,
                       (n, slots, 2)).astype(np.float32) * (probe + 1)
    assert np.array_equal(SimTransport(n).run_reference(sched, buf),
                          ex.run_sim(buf))


def test_fuzz_corpus_sweep_200_schedules():
    """Deterministic acceptance sweep: >= 200 fuzzed (schedule,
    topology) pairs are bit-exact vs the oracle and cost-safe — the
    fixed-seed floor under the sampled property tests above."""
    checked = 0
    for seed in range(210):
        rng = np.random.default_rng(seed)
        topo = rand_topology(rng)
        sched = rand_schedule(rng, topo.nranks)
        check_conformance(sched, topo, rng)
        checked += 1
    assert checked >= 200


def _small_fuzz_case(seed):
    """Bounded (schedule, topology) pair for the Pallas sweep: the
    single-kernel lowering unrolls every route statically, so each new
    schedule pays a real interpret-mode trace — keep nranks/rounds small
    and let the seeds supply the variety."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 7))
    topo = flat_topology(n)
    slots = int(rng.integers(2, 6))
    rounds = tuple(rand_round(rng, n, slots)
                   for _ in range(int(rng.integers(1, 4))))
    local_pre = (np.stack([rng.permutation(slots) for _ in range(n)])
                 if rng.random() < 0.3 else None)
    local_post = (np.stack([rng.permutation(slots) for _ in range(n)])
                  if rng.random() < 0.3 else None)
    sched = CommSchedule(nranks=n, num_slots=slots, rounds=rounds,
                         name="fuzz.pallas", local_pre=local_pre,
                         local_post=local_post)
    return sched, topo, rng


def check_pallas_conformance(sched, topo, rng) -> None:
    """pallas == shardmap-compiled == rank-by-rank oracle, bitwise.

    The device-side single-kernel lowering (core.pallas_lowering) must
    agree with both the oracle and the compiled simulator on the same
    fuzzed schedule — one kernel launch for the whole round sequence,
    chunked or not."""
    from repro.core.pallas_lowering import get_pallas_exec

    n = sched.nranks
    buf = rng.integers(-8, 8, (n, sched.num_slots, 2)).astype(np.float32)
    want = SimTransport(n).run_reference(sched, buf)
    sim = executor.compile_schedule(sched, optimize=True,
                                    topo=topo).run_sim(buf)
    pex = get_pallas_exec(sched, topo=topo)
    got = np.asarray(pex.run(buf))
    assert np.array_equal(want, sim)
    assert want.tobytes() == got.tobytes()
    got2 = np.asarray(pex.run(buf, chunks=2))      # grid pipeline
    assert want.tobytes() == got2.tobytes()
    assert pex.launches == 2 and pex.jit_traces <= 2


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_fuzzed_schedules_conform_on_pallas(seed):
    """Random bounded schedule: the single-kernel Pallas lowering is
    bit-exact vs the oracle and the compiled simulator."""
    check_pallas_conformance(*_small_fuzz_case(seed))


def test_pallas_fuzz_corpus_sweep():
    """Deterministic floor under the sampled Pallas property test: a
    fixed-seed corpus of bounded fuzz cases, every one bit-exact."""
    for seed in range(25):
        check_pallas_conformance(*_small_fuzz_case(seed))


# ---------------------------------------------------------------------------
# chaos campaigns over fuzzed schedules (core.chaos + core.resilient)
# ---------------------------------------------------------------------------


def _result_region(sched, out):
    out = np.asarray(out)
    rows = sched.result_slots
    return np.stack([out[r, sched.out_offset(r):
                         sched.out_offset(r) + rows]
                     for r in range(sched.nranks)])


def check_chaos_recovery(seed) -> None:
    """The metamorphic chaos oracle on a random schedule: under a
    seeded fault campaign the recovered result region is bitwise
    identical to the fault-free oracle, or a typed
    ``UnrecoverableError`` is raised — never a silent mismatch."""
    from repro.core import chaos
    from repro.core.resilient import (ResilienceOptions, ResilientExec,
                                      UnrecoverableError)

    rng = np.random.default_rng(seed)
    topo = rand_topology(rng)
    sched = rand_schedule(rng, topo.nranks)
    n = sched.nranks
    buf = rng.integers(-8, 8, (n, sched.num_slots, 2)).astype(np.float32)
    want = _result_region(sched, SimTransport(n).run_reference(sched, buf))

    campaign = ("corrupt", "fail", "hang", "mixed")[int(rng.integers(4))]
    persistent = rng.random() < 0.25
    plan = chaos.FaultPlan(
        int(rng.integers(2 ** 31)), campaign,
        times=None if persistent else int(rng.integers(1, 3)),
        max_faults=int(rng.integers(1, 3)), delay_s=0.002)
    transports = {"sim": chaos.wrap(SimTransport(n), plan)}
    if persistent and rng.random() < 0.5:
        # fault the fallback rung too: the typed-error path must fire
        # (or corruption must land outside the verified region)
        transports["reference"] = chaos.wrap(SimTransport(n), plan)
    ex = ResilientExec(
        sched, topo,
        options=ResilienceOptions(verify="full", max_retries=1,
                                  ladder=("sim", "reference"),
                                  backoff_s=1e-5),
        transports=transports)
    try:
        out, report = ex.run(buf)
    except UnrecoverableError as e:
        assert e.report.recovered_with is None     # typed, with the walk
        return
    assert _result_region(sched, out).tobytes() == want.tobytes(), (
        seed, campaign, persistent, report.summary())


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_fuzzed_fault_campaigns_recover_or_raise(seed):
    """Random schedule x random seeded campaign: recovery is bitwise
    or the error is typed — the data-plane analogue of the
    bit-exactness conformance sweep above."""
    check_chaos_recovery(seed)


def test_chaos_fuzz_corpus_sweep():
    """Deterministic floor under the sampled chaos property test: a
    fixed-seed corpus of fault campaigns, every outcome bitwise-or-
    typed."""
    for seed in range(40):
        check_chaos_recovery(seed)


def test_armed_pass_strictly_beats_topology_free_on_staged_multipod():
    """The acceptance bound has teeth: on the width-staggered multi-pod
    staged allgather the armed pass merges rounds the equal-width rule
    must keep apart — strictly fewer rounds AND strictly lower modeled
    time on 2- and 4-pod topologies."""
    from repro.core.algorithms.staged import staggered_pod_allgather

    wins = 0
    for topo in (Topology(8, 4), Topology(16, 4)):
        sched = staggered_pod_allgather(topo)
        free = executor.compile_schedule(sched, optimize=True)
        armed = executor.compile_schedule(sched, optimize=True, topo=topo)
        rng = np.random.default_rng(0)
        buf = rng.integers(-8, 8,
                           (topo.nranks, sched.num_slots, 2)
                           ).astype(np.float32)
        want = SimTransport(topo.nranks).run_reference(sched, buf)
        assert np.array_equal(want, armed.run_sim(buf))
        for s in _PROBE_SLOT_BYTES:
            t_free = free.compiled_schedule.modeled_time(topo, s)
            t_armed = armed.compiled_schedule.modeled_time(topo, s)
            assert t_armed <= t_free * (1 + 1e-9)
        if (armed.rounds_after < free.rounds_after
                and armed.compiled_schedule.modeled_time(topo, 4096)
                < free.compiled_schedule.modeled_time(topo, 4096)):
            wins += 1
    assert wins == 2, "armed pass must strictly win on both topologies"
