"""Persistent-executor compilation tests (core.executor).

The contract: the compiled path — baked tables, vectorized simulator
rounds, local_pre folding, round compaction/fusion, scratch-zero
elision — is bit-exact with the historical rank-by-rank reference
executor for every registered schedule, every topology class, float32
and bfloat16; fusion is *legal* exactly per ``schedule.can_fuse``; and
the process-level executor cache hands back one compiled object per
(schedule content, flags).

The shard_map half of the sweep (fused ppermute lowering vs the same
reference) and the jit trace-count proof run on forced host devices in
tests/device_scripts/check_executor.py via test_shardmap.py.
"""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # dev extra not installed: seeded fallback
    from _hypothesis_stub import given, settings, st

import jax.numpy as jnp

from repro.core import executor
from repro.core.algorithms import REGISTRY
from repro.core.plan import CommGraph, build_plan
from repro.core.schedule import (CommRound, CommSchedule, NotApplicable,
                                 can_fuse, make_round)
from repro.core.topology import Topology, flat_topology, torus_topology
from repro.core.transport import SimTransport


@pytest.fixture(autouse=True)
def _fresh_executor_cache():
    executor.clear_cache()
    yield
    executor.clear_cache()


TOPOS = {
    "flat": flat_topology(8),
    "2pod": Topology(8, 4),
    "3lvl": torus_topology(2, 2, 2),
}
DTYPES = {"float32": np.float32, "bfloat16": jnp.bfloat16}


def _all_schedules(topo):
    out = []
    for coll, algos in REGISTRY.items():
        for name, builder in algos.items():
            try:
                out.append((f"{coll}.{name}", builder(topo)))
            except NotApplicable:
                continue
    rng = np.random.default_rng(7)
    graph = CommGraph.random(topo.nranks, n_local=6, degree=4, rng=rng,
                             dup_frac=0.8)
    for aggregate in (False, True):
        plan = build_plan(graph, topo, aggregate=aggregate)
        out.append((plan.name, plan.schedule))
    return out


# ---------------------------------------------------------------------------
# bit-exactness: fused+compiled == unfused reference (full sim sweep)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topo_name", sorted(TOPOS))
@pytest.mark.parametrize("dt_name", sorted(DTYPES))
def test_compiled_bit_exact_with_reference(topo_name, dt_name):
    topo, dtype = TOPOS[topo_name], DTYPES[dt_name]
    n = topo.nranks
    rng = np.random.default_rng(0)
    tr = SimTransport(n)
    for label, sched in _all_schedules(topo):
        buf = rng.integers(-8, 8,
                           (n, sched.num_slots, 3)).astype(dtype)
        want = tr.run_reference(sched, buf)
        got_fused = tr.run(sched, buf)            # compiled + optimized
        got_plain = executor.compile_schedule(
            sched, optimize=False).run_sim(buf)   # compiled, no peephole
        assert np.array_equal(want, got_fused), (topo_name, label, dt_name)
        assert np.array_equal(want, got_plain), (topo_name, label, dt_name)


def test_reference_buffer_not_mutated():
    topo = TOPOS["2pod"]
    sched = REGISTRY["allreduce"]["ring_rs_ag"](topo)
    buf = np.random.default_rng(1).normal(
        size=(8, sched.num_slots, 2)).astype(np.float32)
    keep = buf.copy()
    SimTransport(8).run(sched, buf)
    assert np.array_equal(buf, keep)


# ---------------------------------------------------------------------------
# fusion legality (schedule.can_fuse) — satellite property tests
# ---------------------------------------------------------------------------


def _rand_round(rng, nranks, num_slots, *, reduce=False, forbid=None):
    """A random valid round: random partial matching + random tables."""
    ranks = list(range(nranks))
    m = int(rng.integers(1, nranks // 2 + 1))
    srcs = list(rng.permutation(ranks)[:m])
    dsts = list(rng.permutation(ranks)[:m])
    edges, send, recv = [], {}, {}
    for s, d in zip(srcs, dsts):
        k = int(rng.integers(1, 3))
        send[s] = list(rng.integers(0, num_slots, k))
        recv[d] = list(rng.permutation(num_slots)[:k])  # distinct targets
        edges.append((int(s), int(d)))
    return make_round(nranks, edges, send, recv, reduce=reduce)


@settings(max_examples=60, deadline=None)
@given(seed=st.integers(0, 2**31), reduce_a=st.booleans(),
       reduce_b=st.booleans())
def test_can_fuse_rejects_by_rule(seed, reduce_a, reduce_b):
    """can_fuse must be exactly: no reduce, disjoint srcs, disjoint
    dsts, and no scatter(i) -> gather(i+1) aliasing."""
    rng = np.random.default_rng(seed)
    n, slots = 8, 5
    a = _rand_round(rng, n, slots, reduce=reduce_a)
    b = _rand_round(rng, n, slots, reduce=reduce_b)
    share_src = bool(a.src_set & b.src_set)
    share_dst = bool(a.dst_set & b.dst_set)
    alias = any(a.writes(r) & b.reads(r)
                for r in a.dst_set & b.src_set)
    expect = not (reduce_a or reduce_b or share_src or share_dst or alias)
    assert can_fuse(a, b) == expect


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2**31))
def test_legal_fusion_is_semantics_preserving(seed):
    """Whenever can_fuse says yes, executing the two rounds as one
    merged round is bit-identical to executing them in sequence."""
    rng = np.random.default_rng(seed)
    n, slots = 8, 5
    a = _rand_round(rng, n, slots)
    b = _rand_round(rng, n, slots)
    if not can_fuse(a, b):
        return
    k = max(a.k, b.k)

    def pad(x):
        out = np.full((n, k), -1, np.int64)
        out[:, : x.shape[1]] = x
        return out

    ga, sa, gb, sb = (pad(a.gather_idx), pad(a.scatter_idx),
                      pad(b.gather_idx), pad(b.scatter_idx))
    in_b = np.zeros(n, bool)
    for s, d in b.perm:
        in_b[s] = True
        in_b[d] = True
    # disjoint src/dst sets => per-rank row merge is well-defined for
    # gather (srcs) and scatter (dsts) separately
    gather = ga.copy()
    scatter = sa.copy()
    for s, _ in b.perm:
        gather[s] = gb[s]
    for _, d in b.perm:
        scatter[d] = sb[d]
    merged = CommRound(perm=a.perm + b.perm, gather_idx=gather,
                       scatter_idx=scatter, reduce=False)
    tr = SimTransport(n)
    buf = rng.normal(size=(n, slots, 2)).astype(np.float32)
    seq = tr.run_reference(
        CommSchedule(nranks=n, num_slots=slots, rounds=(a, b)), buf)
    one = tr.run_reference(
        CommSchedule(nranks=n, num_slots=slots, rounds=(merged,)), buf)
    assert np.array_equal(seq, one)


def test_rejected_fusions_concrete_cases():
    n, slots = 4, 4
    base = make_round(n, [(0, 1)], {0: [0]}, {1: [2]})
    # shared src
    assert not can_fuse(base, make_round(n, [(0, 2)], {0: [1]}, {2: [3]}))
    # shared dst
    assert not can_fuse(base, make_round(n, [(2, 1)], {2: [1]}, {1: [3]}))
    # reduce involved
    assert not can_fuse(base, make_round(n, [(2, 3)], {2: [1]}, {3: [3]},
                                         reduce=True))
    # scatter of round i aliases gather of round i+1 (rank 1 writes row 2
    # then reads it): must execute in two rounds
    assert not can_fuse(base, make_round(n, [(1, 3)], {1: [2]}, {3: [3]}))
    # fully legal: disjoint srcs/dsts, no aliasing
    legal = make_round(n, [(2, 3)], {2: [1]}, {3: [3]})
    assert can_fuse(base, legal)


# ---------------------------------------------------------------------------
# fusion cuts rounds on staged multi-pod schedules
# ---------------------------------------------------------------------------


from repro.core.algorithms.staged import serialized_pod_allgather


def test_fusion_overlaps_disjoint_pod_stages():
    """The serialized two-pod staged allgather fuses back to the
    parallel_fuse'd round count (2*(R-1) -> R-1) bit-exactly, and the
    fused schedule matches the registered hierarchical builder's stage-A
    depth."""
    topo = Topology(8, 4)
    sched = serialized_pod_allgather(topo)
    ex = executor.get_executor(sched)
    assert ex.rounds_before == 6          # 2 pods x (4-1) ring rounds
    assert ex.rounds_after == 3           # pod stages fully overlapped
    rng = np.random.default_rng(3)
    buf = rng.normal(size=(8, 8, 2)).astype(np.float32)
    tr = SimTransport(8)
    assert np.array_equal(tr.run_reference(sched, buf),
                          tr.run(sched, buf))
    # and on a 4-pod topology: 4 serialized stages -> one fused stage
    topo4 = Topology(12, 3)
    ex4 = executor.get_executor(serialized_pod_allgather(topo4))
    assert ex4.rounds_before == 8 and ex4.rounds_after == 2


def test_fusion_never_worsens_modeled_time():
    """Cost-safety of the all-or-nothing drain rule: across the whole
    corpus (incl. real multi-pod staged neighbor plans), compilation
    never raises the alpha-beta modeled time — partial migrations that
    would redistribute edges without deleting a ppermute are rolled
    back.  The already-round-minimal colored neighbor plans therefore
    pass through unchanged."""
    for topo in (Topology(12, 3), Topology(8, 4)):
        for label, sched in _all_schedules(topo):
            ex = executor.get_executor(sched)
            before = sched.modeled_time(topo, 4096)
            after = ex.compiled_schedule.modeled_time(topo, 4096)
            assert after <= before * 1.0001, (label, before, after)
    # and a plan whose coloring is already tight keeps its round count
    rng = np.random.default_rng(0)
    graph = CommGraph.random(12, n_local=6, degree=4, rng=rng,
                             dup_frac=0.8)
    plan = build_plan(graph, Topology(12, 3), aggregate=True)
    assert plan.num_compiled_rounds == plan.num_rounds


def test_armed_corpus_never_worse_than_topology_free():
    """Acceptance invariant of the cost-model-armed pass: over the full
    registry x {flat, 2-pod, 3-level} corpus the armed compilation's
    modeled time is <= the topology-free pass AND <= the unoptimized
    schedule at alpha-dominated, mixed, and beta-dominated slot sizes —
    with bit-exact execution."""
    rng = np.random.default_rng(2)
    for topo in TOPOS.values():
        tr = SimTransport(topo.nranks)
        for label, sched in _all_schedules(topo):
            armed = executor.get_executor(sched, topo=topo)
            free = executor.get_executor(sched)
            buf = rng.integers(-8, 8, (topo.nranks, sched.num_slots, 2)
                               ).astype(np.float32)
            assert np.array_equal(tr.run_reference(sched, buf),
                                  armed.run_sim(buf)), label
            for s in (1, 4096, 1 << 20):
                t_orig = sched.modeled_time(topo, s)
                t_free = free.compiled_schedule.modeled_time(topo, s)
                t_armed = armed.compiled_schedule.modeled_time(topo, s)
                assert t_armed <= t_free * 1.0001, (label, s)
                assert t_armed <= t_orig * 1.0001, (label, s)


def test_armed_fuses_staggered_multipod_stages():
    """The width-staggered serialized staged allgather: the topology-
    free equal-width rule can only partially re-fuse it; the armed pass
    overlaps the wide Bruck rounds with the ring rounds (unequal-width
    whole-round merges) — strictly fewer rounds, strictly lower modeled
    time, bit-exact."""
    from repro.core.algorithms.staged import staggered_pod_allgather

    topo = Topology(8, 4)
    sched = staggered_pod_allgather(topo)
    free = executor.get_executor(sched)
    armed = executor.get_executor(sched, topo=topo)
    assert sched.num_rounds == 5          # 3 ring + 2 bruck rounds
    assert free.rounds_after == 4         # only the w=1 bruck round fuses
    assert armed.rounds_after == 3        # w=2 bruck round overlaps too
    assert armed.armed_merged_rounds >= 1
    assert (armed.compiled_schedule.modeled_time(topo, 4096)
            < free.compiled_schedule.modeled_time(topo, 4096))
    rng = np.random.default_rng(4)
    buf = rng.integers(-8, 8, (8, 8, 2)).astype(np.float32)
    tr = SimTransport(8)
    assert np.array_equal(tr.run_reference(sched, buf), armed.run_sim(buf))


def test_duplicate_reduce_targets_accumulate_like_reference(monkeypatch):
    """With validation off, a reduce round may carry duplicate live
    scatter targets; the vectorized path must fall back to unbuffered
    accumulation and still match the reference loop."""
    monkeypatch.setenv("REPRO_VALIDATE_SCHEDULES", "0")
    n = 3
    gi = np.array([[0, 1], [-1, -1], [-1, -1]], np.int64)
    si = np.array([[-1, -1], [1, 1], [-1, -1]], np.int64)  # dup target 1
    rnd = CommRound(perm=((0, 1),), gather_idx=gi, scatter_idx=si,
                    reduce=True)
    sched = CommSchedule(nranks=n, num_slots=2, rounds=(rnd,))
    rng = np.random.default_rng(11)
    buf = rng.normal(size=(n, 2, 2)).astype(np.float32)
    tr = SimTransport(n)
    assert np.array_equal(tr.run_reference(sched, buf),
                          tr.run(sched, buf))


def test_reduce_rounds_are_never_fused():
    """Reduce rounds act as barriers: disjoint-pod REDUCE stages must
    stay separate (accumulation order is bit-exactness-critical)."""
    n = 8
    rounds = []
    for members in ([0, 1], [4, 5]):
        edges = [(members[0], members[1])]
        rounds.append(make_round(n, edges, {members[0]: [0]},
                                 {members[1]: [0]}, reduce=True))
    sched = CommSchedule(nranks=n, num_slots=2, rounds=tuple(rounds))
    ex = executor.get_executor(sched)
    assert ex.rounds_after == ex.rounds_before == 2


# ---------------------------------------------------------------------------
# local_pre folding
# ---------------------------------------------------------------------------


def test_bruck_local_pre_is_folded():
    sched = REGISTRY["alltoall"]["bruck"](flat_topology(8))
    assert sched.local_pre is not None
    ex = executor.get_executor(sched)
    assert ex.pre_folded and ex.local_pre is None
    assert ex.local_post is not None
    # unoptimized executor keeps the pre-gather
    plain = executor.compile_schedule(sched, optimize=False)
    assert not plain.pre_folded and plain.local_pre is not None


def test_non_bijective_local_pre_not_folded():
    n = 4
    rnd = make_round(n, [(0, 1)], {0: [0]}, {1: [2]})
    pre = np.zeros((n, 3), np.int64)        # all rows read slot 0
    sched = CommSchedule(nranks=n, num_slots=3, rounds=(rnd,),
                         local_pre=pre)
    ex = executor.get_executor(sched)
    assert not ex.pre_folded and ex.local_pre is not None
    rng = np.random.default_rng(5)
    buf = rng.normal(size=(n, 3, 2)).astype(np.float32)
    tr = SimTransport(n)
    assert np.array_equal(tr.run_reference(sched, buf),
                          tr.run(sched, buf))


# ---------------------------------------------------------------------------
# executor cache — satellite tests
# ---------------------------------------------------------------------------


def test_cache_one_executor_per_schedule_content():
    topo = flat_topology(8)
    s1 = REGISTRY["allgather"]["ring"](topo)
    s2 = REGISTRY["allgather"]["ring"](topo)      # independent build
    assert s1 is not s2
    assert s1.fingerprint() == s2.fingerprint()
    ex1 = executor.get_executor(s1)
    assert executor.get_executor(s1) is ex1       # same object
    assert executor.get_executor(s2) is ex1       # content-keyed
    stats = executor.cache_stats()
    assert stats["size"] == 1
    assert stats["misses"] == 1 and stats["hits"] == 2
    # a different schedule compiles separately
    other = REGISTRY["allgather"]["bruck"](topo)
    assert other.fingerprint() != s1.fingerprint()
    assert executor.get_executor(other) is not ex1
    assert executor.cache_stats()["size"] == 2


def test_cache_invalidated_by_validation_flag(monkeypatch):
    sched = REGISTRY["allgather"]["ring"](flat_topology(8))
    ex_on = executor.get_executor(sched)
    monkeypatch.setenv("REPRO_VALIDATE_SCHEDULES", "0")
    ex_off = executor.get_executor(sched)
    assert ex_on is not ex_off
    monkeypatch.setenv("REPRO_VALIDATE_SCHEDULES", "1")
    assert executor.get_executor(sched) is ex_on


@settings(max_examples=20, deadline=None)
@given(pair=st.sampled_from([("flat", "2pod"), ("flat", "3lvl"),
                             ("2pod", "3lvl")]),
       algo=st.sampled_from(["ring", "bruck"]))
def test_cache_keyed_by_topology_distinct_entries_same_numerics(pair, algo):
    """Two distinct topologies compiling the SAME schedule content must
    occupy distinct cache entries (per-geometry armed compilations
    never collide) — and topology-armed vs topology-free likewise —
    while every entry stays bit-identical to the oracle."""
    executor.clear_cache()
    a_name, b_name = pair
    topo_a, topo_b = TOPOS[a_name], TOPOS[b_name]
    sched = REGISTRY["allgather"][algo](flat_topology(8))
    ex_none = executor.get_executor(sched)
    ex_a = executor.get_executor(sched, topo=topo_a)
    ex_b = executor.get_executor(sched, topo=topo_b)
    assert ex_none is not ex_a and ex_none is not ex_b
    assert ex_a is not ex_b
    assert executor.cache_stats()["size"] == 3
    # repeat lookups hit the same per-geometry entries
    assert executor.get_executor(sched, topo=topo_a) is ex_a
    assert executor.get_executor(sched, topo=topo_b) is ex_b
    assert executor.get_executor(sched) is ex_none
    assert executor.cache_stats()["size"] == 3
    # identical numerics across all three compilations
    rng = np.random.default_rng(9)
    buf = rng.integers(-8, 8, (8, sched.num_slots, 2)).astype(np.float32)
    want = SimTransport(8).run_reference(sched, buf)
    for ex in (ex_none, ex_a, ex_b):
        assert np.array_equal(want, ex.run_sim(buf))


def test_cache_same_geometry_different_instances_share_entry():
    """The cache keys on the topology's geometry fingerprint, not
    object identity: two equal Topology instances share one executor;
    a same-shape topology with different link models does not."""
    from repro.core.topology import LinkModel, TopoLevel

    sched = REGISTRY["allgather"]["ring"](flat_topology(8))
    t1, t2 = Topology(8, 4), Topology(8, 4)
    assert executor.get_executor(sched, topo=t1) is \
        executor.get_executor(sched, topo=t2)
    slow_dcn = Topology(
        8, 4, levels=(TopoLevel("dcn", 2, LinkModel(1e-4, 1e-7), True),
                      TopoLevel("ici", 4)))
    assert executor.get_executor(sched, topo=slow_dcn) is not \
        executor.get_executor(sched, topo=t1)


def test_cache_invalidated_by_optimize_flag(monkeypatch):
    sched = REGISTRY["allgather"]["ring"](flat_topology(8))
    ex_opt = executor.get_executor(sched)
    monkeypatch.setenv("REPRO_EXEC_OPTIMIZE", "0")
    ex_plain = executor.get_executor(sched)
    assert ex_plain is not ex_opt and not ex_plain.optimize


def test_sim_run_counter_and_stats():
    sched = REGISTRY["allreduce"]["ring_rs_ag"](flat_topology(8))
    tr = SimTransport(8)
    buf = np.ones((8, sched.num_slots, 1), np.float32)
    tr.run(sched, buf)
    tr.run(sched, buf)
    ex = executor.get_executor(sched)
    assert ex.sim_runs == 2
    st_ = ex.stats()
    assert st_["rounds_before"] == sched.num_rounds
    assert st_["trace_count"] == 0


# ---------------------------------------------------------------------------
# shard_map round lowerings: in-place slices vs index tables
# ---------------------------------------------------------------------------


SLICE_COLLECTIVES = ("allreduce", "reduce_scatter", "allgather", "alltoall")
# the registered schedules whose compiled rounds do not all move one run
# of consecutive slots on every rank (Bruck rotations), by collective
TABLE_LOWERED = {"allgather.bruck", "allgather.hierarchical",
                 "alltoall.bruck"}
# exact slice-round counts pinned for the grad-sync schedules (4 ranks)
SLICE_ROUNDS_4 = {"allreduce.recursive_halving_doubling": 4,
                  "allreduce.ring_rs_ag": 6}


@pytest.fixture(scope="module")
def shardmap_lowerings():
    """One subprocess per rank count (forced host devices): every
    schedule's shard_map result against the reference, its round
    counters, and the compiled HLO's op counts on 4 ranks."""
    import json

    from test_shardmap import run_script
    return {n: json.loads(run_script("check_slice_rounds.py",
                                     str(n)).strip().splitlines()[-1])
            for n in (4, 8)}


@pytest.mark.parametrize("nranks", [4, 8])
@pytest.mark.parametrize("label", [f"{c}.{a}" for c in SLICE_COLLECTIVES
                                   for a in REGISTRY[c]])
def test_shardmap_slice_rounds_bit_exact(shardmap_lowerings, nranks,
                                         label):
    """Each round is lowered as in-place dynamic slices when every rank
    moves one run of consecutive slots, through index tables otherwise;
    either way the shard_map result is bitwise the reference's."""
    got = shardmap_lowerings[nranks]["schedules"][label]
    assert got["bitwise"], (nranks, label)
    assert got["slice_rounds"] + got["table_rounds"] == got["rounds"]
    if label in TABLE_LOWERED:
        assert got["table_rounds"] > 0, (nranks, label, got)
    else:
        assert got["table_rounds"] == 0, (nranks, label, got)
    if nranks == 4 and label in SLICE_ROUNDS_4:
        assert got["slice_rounds"] == SLICE_ROUNDS_4[label], got


def test_shardmap_rhd_allreduce_hlo_has_no_table_ops(shardmap_lowerings):
    """The 4-rank recursive_halving_doubling mpix_allreduce compiles with
    no gather, no scatter and no concatenate (no scratch-padded copy of
    the buffer): all its rounds take the slice lowering."""
    assert shardmap_lowerings[4]["hlo"] == {
        "gather": 0, "scatter": 0, "concatenate": 0}


def test_slice_rounds_counted_in_stats():
    """``stats()`` counts the rounds of each lowering; the unoptimized
    executor keeps the historical table lowering for every round."""
    topo = flat_topology(8)
    ag = executor.get_executor(REGISTRY["allgather"]["bruck"](topo))
    st_ = ag.stats()
    assert (st_["slice_rounds"], st_["table_rounds"]) == (1, 2)
    plain = executor.compile_schedule(
        REGISTRY["allreduce"]["ring_rs_ag"](topo), optimize=False)
    assert (plain.slice_rounds, plain.table_rounds) == (0, 14)


# ---------------------------------------------------------------------------
# schedule fingerprint
# ---------------------------------------------------------------------------


def test_fingerprint_ignores_name_tracks_content():
    topo = flat_topology(8)
    a = REGISTRY["allgather"]["ring"](topo)
    import dataclasses
    renamed = dataclasses.replace(a, name="something.else")
    assert renamed.fingerprint() == a.fingerprint()
    # content drift (one table entry) changes the fingerprint
    rnd = a.rounds[0]
    g = rnd.gather_idx.copy()
    g[0, 0] = (g[0, 0] + 1) % a.num_slots
    mutated = dataclasses.replace(
        a, rounds=(dataclasses.replace(rnd, gather_idx=g),) + a.rounds[1:])
    assert mutated.fingerprint() != a.fingerprint()


# ---------------------------------------------------------------------------
# byte_count precedence — satellite regression
# ---------------------------------------------------------------------------


def test_byte_count_honors_slot_bytes_with_payload():
    """A round carrying both ``payload`` and schedule-level
    ``slot_bytes`` must bill the per-slot true byte widths, not
    ``slots * elem_bytes``."""
    n = 2
    gi = np.array([[0, 1], [-1, -1]], np.int64)
    si = np.array([[-1, -1], [0, 1]], np.int64)
    rnd = CommRound(perm=((0, 1),), gather_idx=gi, scatter_idx=si,
                    payload=np.array([2, 0], np.int64))
    slot_bytes = np.array([100, 7], np.int64)
    sched = CommSchedule(nranks=n, num_slots=2, rounds=(rnd,),
                         slot_bytes=slot_bytes)
    # slot widths win over the elem_bytes estimate: 100 + 7
    assert sched.byte_count(4) == 107
    # payload truncates padded gather entries: only the first true slot
    rnd_pad = CommRound(perm=((0, 1),), gather_idx=gi, scatter_idx=si,
                        payload=np.array([1, 0], np.int64))
    sched_pad = CommSchedule(nranks=n, num_slots=2, rounds=(rnd_pad,),
                             slot_bytes=slot_bytes)
    assert sched_pad.byte_count(4) == 100
    # without slot_bytes the historical payload * elem_bytes path holds
    sched_plain = CommSchedule(nranks=n, num_slots=2, rounds=(rnd,))
    assert sched_plain.byte_count(4) == 8
