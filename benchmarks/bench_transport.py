"""Persistent-executor transport benchmark (the BENCH_transport.json
artifact).

Sections, tracking the compiled-executor wins from that PR onward:

  * ``fusion``    — rounds before/after compilation for every registered
                    schedule + both neighborhood plan modes on a spread
                    of topologies (the alpha-term cut; includes ≥1
                    staged multi-pod plan that actually loses rounds).
  * ``sim_exec``  — wall time of executing the whole schedule corpus
                    through the vectorized SimTransport vs the
                    rank-by-rank reference loop (the tuner/CI speedup).
  * ``shardmap``  — jit calls vs executor traces on the 8-host-device
                    mesh: repeated steps of one compiled collective must
                    lower exactly once per (shape, dtype).
  * ``pallas``    — device-side single-kernel transport: R compiled
                    rounds -> 1 ``pallas_call`` per run over the corpus,
                    and the fused allreduce->rmsnorm epilogue's modeled
                    HBM-traffic win ((P+1)·T vs (P+3)·T).  Both claims
                    are machine-independent and BLOCKING under
                    ``--check`` (the CI ``--check-transport`` gate).
  * ``fleet``     — online tuning (the drift-healing PR): a deterministic
                    DCN degradation must heal a strict SUBSET of the
                    tuned table (cells re-measured vs total), and a pod
                    loss must re-derive every registered schedule
                    bit-exact for the shrunk topology.  Model-level,
                    machine-independent, BLOCKING under ``--check``.
  * ``chaos``     — resilience (the fault-injection PR): seeded fault
                    campaigns (corrupt / fail / hang / mixed) against
                    the sim substrate must recover BITWISE-identical
                    results through the verify->retry->fallback ladder;
                    persistent faults must end in a typed
                    ``UnrecoverableError`` after a bounded walk; and
                    verification pricing must stay ordered
                    (off = 0 < canary < full).  BLOCKING under
                    ``--check``.
  * ``serve``     — continuous batching (the serving PR, see
                    benchmarks.bench_serve): a seeded Poisson
                    multi-tenant trace drained by the disaggregated
                    prefill/decode engine — every arrival completes,
                    every KV block transfer lands bit-exact vs the
                    gather oracle, locality-aware plans never message
                    DCN more than standard (and dedupe shared-prefix
                    bytes strictly), and the chaos-under-load trace
                    degrades-and-recovers.  BLOCKING under ``--check``.

CLI:
    PYTHONPATH=src python -m benchmarks.bench_transport \
        --json BENCH_transport.json [--check BENCH_transport.json]

``--check`` compares sim-exec wall time against a committed baseline and
prints a (non-blocking) GitHub-style ``::warning`` on a >2x regression —
walltimes are machine-dependent, the warning is a trend signal, not a
gate.  A missing/malformed baseline file, however, exits non-zero: that
is a wiring bug, and silently skipping it would disarm the trend job.
"""
from __future__ import annotations

import json
import os
import sys
import time

# forced host devices for the shardmap section (no-op if jax already
# initialized by an earlier sibling import, e.g. bench_tuner in run.py)
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=8").strip()

import numpy as np

from benchmarks.common import emit

SIM_REPEATS = 3
FEAT = 4


def _topos():
    from repro.core.topology import Topology, flat_topology, torus_topology
    return {
        "flat8": flat_topology(8),
        "pods8x4": Topology(8, 4),
        "odd12x3": Topology(12, 3),
        "torus2x2x4": torus_topology(2, 2, 4),
    }


def _schedules(topo):
    from repro.core.algorithms import REGISTRY
    from repro.core.plan import CommGraph, build_plan
    from repro.core.schedule import NotApplicable

    out = []
    for coll, algos in REGISTRY.items():
        for name, builder in algos.items():
            try:
                out.append((f"{coll}.{name}", builder(topo)))
            except NotApplicable:
                continue
    if topo.npods > 1:
        # the deliberately serialized per-pod staging: the corpus entry
        # proving the executor recovers the parallel_fuse'd overlap —
        # and its width-staggered sibling, which only the cost-model-
        # armed pass can overlap fully (unequal-width merges)
        from repro.core.algorithms.staged import (serialized_pod_allgather,
                                                  staggered_pod_allgather)
        out.append(("allgather.staged_naive",
                    serialized_pod_allgather(topo)))
        out.append(("allgather.staged_staggered",
                    staggered_pod_allgather(topo)))
    rng = np.random.default_rng(0)
    graph = CommGraph.random(topo.nranks, n_local=6,
                             degree=min(topo.nranks - 1, 4), rng=rng,
                             dup_frac=0.8)
    for aggregate in (False, True):
        plan = build_plan(graph, topo, aggregate=aggregate)
        out.append((plan.name, plan.schedule))
    return out


def bench_fusion() -> dict:
    """Rounds before/after compilation per (topology, schedule), for
    both the topology-free pass and the cost-model-armed pass."""
    from repro.core import executor

    fusion: dict = {}
    fused_schedules = 0
    armed_wins = 0
    for tname, topo in _topos().items():
        for label, sched in _schedules(topo):
            ex = executor.get_executor(sched)
            armed = executor.get_executor(sched, topo=topo)
            key = f"{tname}.{label}"
            fusion[key] = {"before": ex.rounds_before,
                           "after": ex.rounds_after,
                           "after_armed": armed.rounds_after,
                           "migrated_edges": ex.migrated_edges,
                           "armed_merged_rounds": armed.armed_merged_rounds,
                           "armed_split_edges": armed.armed_split_edges,
                           "pre_folded": ex.pre_folded}
            if ex.rounds_after < ex.rounds_before:
                fused_schedules += 1
                emit("transport", f"{key}.rounds",
                     f"{ex.rounds_before}->{ex.rounds_after}", "rounds",
                     "fused")
            if armed.rounds_after < ex.rounds_after:
                armed_wins += 1
                emit("transport", f"{key}.rounds_armed",
                     f"{ex.rounds_after}->{armed.rounds_after}", "rounds",
                     "topology-armed")
    emit("transport", "fusion.schedules_with_round_cut", fused_schedules)
    emit("transport", "fusion.schedules_armed_round_cut", armed_wins)
    assert fused_schedules >= 1, (
        "at least one staged multi-pod schedule must lose rounds to fusion")
    assert armed_wins >= 1, (
        "the armed pass must cut rounds beyond the topology-free pass "
        "on at least one staged multi-pod schedule")
    return fusion


def bench_sim_exec() -> dict:
    """Vectorized simulator wall time over the whole corpus (and the
    reference-loop time it replaced)."""
    from repro.core import executor
    from repro.core.transport import SimTransport

    rng = np.random.default_rng(1)
    work = []
    for tname, topo in _topos().items():
        for label, sched in _schedules(topo):
            buf = rng.normal(size=(topo.nranks, sched.num_slots, FEAT)) \
                .astype(np.float32)
            work.append((topo.nranks, sched, buf))
    # one-time persistent-init cost (fingerprint + peephole + baking),
    # measured separately from the steady state it buys
    executor.clear_cache()
    t0 = time.perf_counter()
    for n, sched, buf in work:
        executor.get_executor(sched)
    compile_s = time.perf_counter() - t0
    # steady state: the path the tuner's timing loops and the sweeps pay
    t0 = time.perf_counter()
    for _ in range(SIM_REPEATS):
        for n, sched, buf in work:
            SimTransport(n).run(sched, buf)
    compiled_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(SIM_REPEATS):
        for n, sched, buf in work:
            SimTransport(n).run_reference(sched, buf)
    reference_s = time.perf_counter() - t0
    out = {
        "schedules": len(work),
        "repeats": SIM_REPEATS,
        "compile_total_s": round(compile_s, 4),
        "compiled_total_s": round(compiled_s, 4),
        "reference_total_s": round(reference_s, 4),
        "speedup": round(reference_s / max(compiled_s, 1e-9), 2),
    }
    emit("transport", "sim_exec.compile_s", out["compile_total_s"], "s",
         "one-time")
    emit("transport", "sim_exec.compiled_s", out["compiled_total_s"], "s")
    emit("transport", "sim_exec.reference_s", out["reference_total_s"], "s")
    emit("transport", "sim_exec.speedup", out["speedup"], "x")
    return out


def bench_makespan() -> dict:
    """Pipelined-pass (PR 6) section: per corpus schedule, the armed
    serial time plus consumer compute vs the packed makespan with a
    splittable tail event, at a beta-dominated slot size — plus the
    MoE-dispatch overlap win (row-chunked software pipeline priced by
    ``chunked_makespan``, the tuner's OVERLAP model).  Both numbers are
    pure alpha-beta model, so the asserts are machine-independent and
    blocking: the makespan chain must hold pointwise and compute-comm
    overlap must buy a strict win on the dispatch path."""
    import dataclasses

    from repro.core import executor
    from repro.core.schedule import ComputeEvent

    slot = float(1 << 20)
    out: dict = {"slot_bytes": int(slot), "schedules": {}}
    strict_wins = 0
    for tname, topo in _topos().items():
        for label, base in _schedules(topo):
            ev = ComputeEvent("consumer", base.modeled_time(topo, 4096.0),
                              after_round=-1, splittable=True, parts=4)
            sched = dataclasses.replace(base, compute_events=(ev,))
            ex = executor.get_executor(sched, topo=topo)
            serial = (ex.compiled_schedule.modeled_time(topo, slot)
                      + ev.seconds)
            mk = ex.makespan(slot)
            assert mk <= serial * (1 + 1e-9), (tname, label, mk, serial)
            key = f"{tname}.{label}"
            out["schedules"][key] = {
                "serial_s": serial, "makespan_s": mk,
                "tail_parts": ex.pipeline_tail_parts}
            if mk < serial * (1 - 1e-9):
                strict_wins += 1
                emit("transport", f"{key}.makespan",
                     round(serial / mk, 3), "x", "overlap win")
    out["strict_wins"] = strict_wins
    assert strict_wins >= 1, (
        "the pipelined pass must strictly beat armed-serial + compute "
        "on at least one corpus schedule")
    emit("transport", "makespan.strict_wins", strict_wins)

    # MoE dispatch path: hierarchical alltoall chunked against an
    # expert-MLP-sized compute block (balanced pipeline regime)
    from repro.core.algorithms import REGISTRY
    from repro.core.topology import Topology

    topo = Topology(8, 4)
    sched = REGISTRY["alltoall"]["hierarchical"](topo)
    ex = executor.get_executor(sched, topo=topo)
    compute_s = ex.compiled_schedule.modeled_time(topo, slot)
    times = {p: ex.chunked_makespan(slot, p, compute_s)
             for p in (1, 2, 4, 8)}
    best = min(times, key=lambda p: (times[p], p))
    win = times[best] < times[1] * (1 - 1e-3)
    out["moe_overlap"] = {
        "schedule": sched.name, "compute_s": compute_s,
        "times_s": {f"p{p}": t for p, t in times.items()},
        "best_parts": best, "win": bool(win),
        "speedup": round(times[1] / times[best], 3)}
    assert win, (
        "MoE-dispatch chunking must strictly beat the monolithic "
        f"alltoall + compute at {int(slot)}B: {times}")
    emit("transport", "makespan.moe_overlap.speedup",
         out["moe_overlap"]["speedup"], "x",
         f"p{best} vs p1 on {sched.name}")
    return out


def bench_shardmap_traces() -> dict:
    """Steps vs traces for one jitted compiled collective."""
    import jax

    from repro import compat
    from repro.core import executor
    from repro.core.algorithms import REGISTRY
    from repro.core.topology import flat_topology
    from repro.core.transport import ShardMapTransport

    n = 8
    if jax.device_count() < n:
        emit("transport", "shardmap.skipped", 1, "", "needs 8 devices")
        return {"skipped": True}
    from jax.sharding import PartitionSpec as P

    mesh = compat.make_mesh((n,), ("bench",), devices=jax.devices()[:n])
    sched = REGISTRY["allreduce"]["ring_rs_ag"](flat_topology(n))
    executor.clear_cache()
    tr = ShardMapTransport(n, "bench")
    f = jax.jit(compat.shard_map(
        lambda b: tr.run(sched, b), mesh=mesh,
        in_specs=P("bench"), out_specs=P("bench"), check_vma=False))
    x = np.ones((n * sched.num_slots, FEAT), np.float32)
    calls = 6
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        for _ in range(calls):
            jax.block_until_ready(f(x))
    elapsed = time.perf_counter() - t0
    traces = executor.get_executor(sched).trace_count
    out = {"calls": calls, "traces": traces,
           "total_s": round(elapsed, 4)}
    emit("transport", "shardmap.calls", calls)
    emit("transport", "shardmap.traces", traces, "",
         "1 trace per (schedule, shape, dtype)")
    assert traces == 1, f"expected one trace for {calls} calls, got {traces}"
    return out


def bench_pallas() -> dict:
    """Device-side transport section (the single-kernel lowering PR).

    Two sub-claims, both model-level and machine-independent, both
    blocking under ``--check``:

      * launch amortization — for a spread of corpus schedules, R
        compiled rounds execute as exactly ONE ``pallas_call`` per run
        (``PallasExec.launches``), with one jit trace across repeats
        (R -> 1 is the alpha-term win the shardmap substrate cannot
        reach: it pays one collective launch per round);
      * fused rmsnorm epilogue — the allreduce terminal round running
        inside the rmsnorm kernel saves one full write+read of the
        reduced tensor: modeled HBM traffic (P+1)·T vs (P+3)·T, a
        strict win for every P.  Interpreter walltimes for the fused
        and unfused paths are recorded as a trend signal only (on a
        CPU host they time the Pallas interpreter, not the device).
    """
    from repro.core import executor, pallas_lowering
    from repro.core.algorithms import REGISTRY
    from repro.core.topology import Topology, flat_topology

    pallas_lowering.clear_cache()
    corpus = [
        ("flat8.allreduce.ring_rs_ag", flat_topology(8),
         REGISTRY["allreduce"]["ring_rs_ag"]),
        ("flat8.allgather.bruck", flat_topology(8),
         REGISTRY["allgather"]["bruck"]),
        ("pods8x4.alltoall.hierarchical", Topology(8, 4),
         REGISTRY["alltoall"]["hierarchical"]),
        ("pods8x4.allgather.staged", Topology(8, 4),
         REGISTRY["allgather"]["staged"]),
    ]
    rng = np.random.default_rng(2)
    runs = 3
    launches: dict = {}
    for key, topo, builder in corpus:
        sched = builder(topo)
        pex = pallas_lowering.get_pallas_exec(sched, topo=topo)
        buf = rng.normal(size=(topo.nranks, sched.num_slots, FEAT)) \
            .astype(np.float32)
        t0 = time.perf_counter()
        for _ in range(runs):
            pex.run(buf)
        elapsed = time.perf_counter() - t0
        per_run = pex.launches / runs
        launches[key] = {
            "rounds": int(pex.rounds),
            "runs": runs,
            "launches_per_run": per_run,
            "jit_traces": int(pex.jit_traces),
            "total_s": round(elapsed, 4),
        }
        assert per_run == 1, (key, pex.launches, runs)
        assert pex.jit_traces == 1, (key, pex.jit_traces)
        emit("transport", f"pallas.{key}.launches",
             f"{pex.rounds}->1", "launches/run", "single kernel")
    assert any(v["rounds"] > 1 for v in launches.values()), (
        "corpus must contain a genuinely multi-round schedule")

    # fused epilogue: modeled HBM traffic + interpreter walltime trend
    from repro.kernels.rmsnorm import ops as rms_ops
    import jax
    import jax.numpy as jnp

    P_, R, d = 8, 256, 512
    parts = jnp.asarray(rng.normal(size=(P_, R, d)), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    elem = 4
    tensor_b = R * d * elem
    # unfused: read P partials, write the reduced tensor, read it back,
    # write the normalized output; fused: read P partials, write output
    unfused_b = (P_ + 3) * tensor_b
    fused_b = (P_ + 1) * tensor_b

    fused_fn = jax.jit(lambda p, s: rms_ops.rmsnorm_allreduce(p, s))
    unfused_fn = jax.jit(
        lambda p, s: rms_ops.rmsnorm(jnp.sum(p, axis=0), s))
    jax.block_until_ready(fused_fn(parts, scale))
    jax.block_until_ready(unfused_fn(parts, scale))
    t0 = time.perf_counter()
    for _ in range(runs):
        jax.block_until_ready(fused_fn(parts, scale))
    fused_s = (time.perf_counter() - t0) / runs
    t0 = time.perf_counter()
    for _ in range(runs):
        jax.block_until_ready(unfused_fn(parts, scale))
    unfused_s = (time.perf_counter() - t0) / runs

    epilogue = {
        "partials": P_, "tensor_bytes": tensor_b,
        "unfused_hbm_bytes": unfused_b, "fused_hbm_bytes": fused_b,
        "modeled_win": round(unfused_b / fused_b, 4),
        "win": bool(fused_b < unfused_b),
        "fused_walltime_s": round(fused_s, 5),
        "unfused_walltime_s": round(unfused_s, 5),
    }
    assert epilogue["win"] and epilogue["modeled_win"] > 1.0, epilogue
    emit("transport", "pallas.epilogue.modeled_win",
         epilogue["modeled_win"], "x", "HBM traffic")
    emit("transport", "pallas.epilogue.walltime",
         round(unfused_s / max(fused_s, 1e-9), 3), "x",
         "interpreter trend only")
    return {"launches": launches, "epilogue": epilogue}


def bench_fleet() -> dict:
    """Fleet-scale tuning section (the online drift-healing PR).

    Deterministic on the model substrate (``LinkFault`` +
    ``model_timer``), so every number is machine-independent and the
    claims are BLOCKING under ``--check``:

      * scoped heal — a DCN bandwidth collapse (beta x16) must re-measure
        strictly fewer table cells than the table holds (alpha-dominated
        small buckets are unaffected by a beta drift; a full re-tune
        means the scoping broke) while still bumping the generation and
        evicting the stale geometry's compiled plans/executors;
      * elastic re-derivation — dropping a whole pod must re-derive
        every registered schedule for the surviving topology, and each
        re-derived schedule must be bit-exact (fingerprint-equal) with
        a fresh build on that topology.
    """
    import tempfile
    from pathlib import Path

    from repro.core.algorithms import REGISTRY
    from repro.core.linkprobe import model_timer
    from repro.core.topology import DCN_LINK, ICI_LINK, TopoLevel, Topology
    from repro.runtime.elastic import ElasticScheduleSet
    from repro.runtime.fault import LinkFault
    from repro.runtime.tuning_daemon import TuningDaemon

    base = Topology.from_levels([
        TopoLevel("dcn", 2, DCN_LINK, dcn=True),
        TopoLevel("ici", 4, ICI_LINK)])
    fault = LinkFault()
    with tempfile.TemporaryDirectory() as td:
        daemon = TuningDaemon(
            base, path=Path(td) / "tuned.json", force_model=True,
            timer=model_timer(base, fault=fault), repeats=1)
        fault.degrade(0, beta_scale=16.0)
        report = daemon.probe_and_heal(step=1)
    heal = {
        "drifted_levels": list(report.drifted_levels),
        "cells_total": report.total_cells,
        "cells_affected": len(report.affected_cells),
        "cells_retuned": len(report.retuned_cells),
        "generation": report.generation,
        "invalidated": report.invalidated,
        "scoped": bool(
            0 < len(report.affected_cells) < report.total_cells),
    }
    assert heal["scoped"], heal
    assert heal["generation"] >= 1 and heal["cells_retuned"] >= 1, heal
    emit("transport", "fleet.heal.cells",
         f"{heal['cells_retuned']}/{heal['cells_total']}", "cells",
         "scoped re-measure")
    emit("transport", "fleet.heal.invalidated",
         heal["invalidated"]["executors"], "executors", "stale geometry")

    entries = {"grad_sync": ("allreduce", "ring_rs_ag"),
               "ep_dispatch": ("alltoall", "pairwise")}
    schedules = ElasticScheduleSet(daemon.topo, entries)
    swap = schedules.shrink([0, 1, 2, 3])       # pod 0 dies
    bit_exact = all(
        schedules.schedule_for(name).fingerprint()
        == REGISTRY[coll][algo](schedules.topo).fingerprint()
        for name, (coll, algo) in schedules.entries.items())
    elastic = {
        "lost_ranks": list(swap.lost_ranks),
        "old_fingerprint": swap.old_fingerprint,
        "new_fingerprint": swap.new_fingerprint,
        "rederived": len(swap.rederived),
        "invalidated": swap.invalidated,
        "generation": swap.generation,
        "bit_exact": bool(bit_exact),
    }
    assert elastic["rederived"] >= 1 and elastic["bit_exact"], elastic
    emit("transport", "fleet.elastic.rederived", elastic["rederived"],
         "schedules", f"-> {swap.new_fingerprint}")
    return {"heal": heal, "elastic": elastic}


def bench_chaos() -> dict:
    """Chaos-resilience section (the fault-injection PR).

    Deterministic on the sim substrate (seeded ``FaultPlan`` + sim /
    reference rungs), so every claim is machine-independent and
    BLOCKING under ``--check``:

      * every seeded campaign (corrupt / fail / hang / mixed) recovers
        a result region **bitwise identical** to the fault-free oracle;
      * a persistent fault on every rung raises the typed
        ``UnrecoverableError`` after a BOUNDED ladder walk (rungs x
        (1 + retries) attempts — backoff can't spin forever);
      * verification pricing (``tuner.verify_overhead_s``): canary
        costs a strict fraction of the collective it protects and full
        verification strictly more than canary (off = 0).
    """
    from repro.core import chaos, tuner
    from repro.core.algorithms import REGISTRY
    from repro.core.resilient import (ResilienceOptions, ResilientExec,
                                      UnrecoverableError)
    from repro.core.topology import flat_topology
    from repro.core.transport import SimTransport

    topo = flat_topology(8)
    sched = REGISTRY["allgather"]["ring"](topo)
    rng = np.random.default_rng(0)
    buf = rng.integers(-8, 8,
                       (8, sched.num_slots, FEAT)).astype(np.float32)

    def region(out):
        out = np.asarray(out)
        rows = sched.result_slots
        return np.stack([out[r, sched.out_offset(r):
                             sched.out_offset(r) + rows]
                         for r in range(sched.nranks)])

    want = region(SimTransport(8).run_reference(sched, buf))
    campaigns = {}
    for campaign in ("corrupt", "fail", "hang", "mixed"):
        ok, max_attempts, retries = True, 0, 0
        t0 = time.time()
        for seed in range(5):
            plan = chaos.FaultPlan(seed, campaign, delay_s=0.002)
            ex = ResilientExec(
                sched, topo,
                options=ResilienceOptions(verify="full",
                                          ladder=("sim", "reference"),
                                          backoff_s=1e-5),
                transports={"sim": chaos.wrap(SimTransport(8), plan)})
            out, rep = ex.run(buf)
            ok &= region(out).tobytes() == want.tobytes()
            max_attempts = max(max_attempts, len(rep.attempts))
            retries += rep.retries
        campaigns[campaign] = {
            "recovered_bitwise": bool(ok),
            "max_attempts": max_attempts,
            "retries": retries,
            "walltime_s": round(time.time() - t0, 4),
        }
        assert ok, (campaign, campaigns[campaign])
        emit("transport", f"chaos.{campaign}.recovered",
             "bitwise" if ok else "MISMATCH", "",
             f"{retries} retries over 5 seeds")
    # persistent fault on every rung -> typed error, bounded walk
    plan = chaos.FaultPlan(0, "fail", times=None)
    wrapped = chaos.wrap(SimTransport(8), plan)
    opts = ResilienceOptions(verify="off", max_retries=1,
                             ladder=("sim", "reference"), backoff_s=1e-5)
    bound = len(opts.ladder) * (opts.max_retries + 1)
    try:
        ResilientExec(sched, None, options=opts,
                      transports={"sim": wrapped,
                                  "reference": wrapped}).run(buf)
        unrec = {"typed": False, "attempts": 0, "bounded": False}
    except UnrecoverableError as e:
        att = len(e.report.attempts)
        unrec = {"typed": True, "attempts": att,
                 "bounded": att == bound}
    assert unrec["typed"] and unrec["bounded"], unrec
    emit("transport", "chaos.unrecoverable",
         f"{unrec['attempts']} attempts", "",
         "typed error, bounded walk")
    # verification pricing: canary is a strict fraction of the
    # collective; full strictly dearer than canary
    slot_nbytes = 1 << 20
    t_coll = sched.modeled_time(topo, slot_nbytes)
    canary_s = tuner.verify_overhead_s(sched, topo,
                                       slot_nbytes=slot_nbytes,
                                       verify="canary")
    full_s = tuner.verify_overhead_s(sched, topo,
                                     slot_nbytes=slot_nbytes,
                                     verify="full")
    pricing = {
        "modeled_collective_s": t_coll,
        "off_s": tuner.verify_overhead_s(sched, topo,
                                         slot_nbytes=slot_nbytes,
                                         verify="off"),
        "canary_s": canary_s,
        "full_s": full_s,
        "canary_frac": round(canary_s / t_coll, 6),
        "full_frac": round(full_s / t_coll, 6),
    }
    assert pricing["off_s"] == 0.0
    assert 0.0 < pricing["canary_frac"] < 0.5 < pricing["full_frac"], \
        pricing
    emit("transport", "chaos.verify.canary",
         pricing["canary_frac"], "x collective", "O(result) scan")
    emit("transport", "chaos.verify.full",
         pricing["full_frac"], "x collective", "reference re-execution")
    return {"campaigns": campaigns, "unrecoverable": unrec,
            "verify_pricing": pricing}


def payload() -> dict:
    from repro.core import executor

    t0 = time.time()
    data = {"schema": 1, "fusion": bench_fusion()}
    # snapshot BEFORE the timing sections (they clear_cache() to measure
    # cold-compile cost, which would zero this telemetry)
    data["executor_cache"] = {
        k: v for k, v in executor.cache_stats().items() if k != "executors"}
    data["makespan"] = bench_makespan()
    data["pallas"] = bench_pallas()
    data["fleet"] = bench_fleet()
    data["chaos"] = bench_chaos()
    from benchmarks.bench_serve import bench_serve
    data["serve"] = bench_serve()
    data["sim_exec"] = bench_sim_exec()
    data["shardmap"] = bench_shardmap_traces()
    data["elapsed_s"] = round(time.time() - t0, 3)
    return data


def check_against(baseline_path: str, data: dict) -> None:
    """Trend check against the committed baseline.

    The *speedup* comparison stays non-blocking (walltimes are
    machine-dependent; a >2x ratio drop prints a GitHub ``::warning``
    and the run continues).  A missing or malformed baseline file, or a
    baseline without the speedup field, is a CI-wiring bug, not a trend
    — it exits non-zero (SystemExit) instead of silently passing, so a
    deleted/corrupted ``BENCH_transport.json`` cannot turn the trend
    job into a no-op."""
    try:
        with open(baseline_path) as fh:
            base = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        raise SystemExit(
            f"--check: BENCH_transport baseline unreadable "
            f"({baseline_path}: {e})")
    old = base.get("sim_exec", {}).get("speedup")
    new = data.get("sim_exec", {}).get("speedup")
    if not old:
        raise SystemExit(
            f"--check: BENCH_transport baseline {baseline_path} lacks "
            f"sim_exec.speedup (got {old!r})")
    if not new:
        raise SystemExit(
            f"--check: current run's payload lacks sim_exec.speedup "
            f"(got {new!r}); the baseline {baseline_path} is fine")
    if float(new) * 2.0 < float(old):
        print(f"::warning::sim-exec speedup regressed >2x: "
              f"{new:.2f}x vs baseline {old:.2f}x "
              f"(walltime {data['sim_exec']['compiled_total_s']:.3f}s)",
              file=sys.stderr)
    else:
        print(f"# sim-exec speedup {new:.2f}x within 2x of baseline "
              f"{old:.2f}x", file=sys.stderr)
    # makespan section: pure model numbers, machine-independent, so a
    # lost compute-comm-overlap win IS a blocking regression (unlike
    # the walltime trend above)
    mk = data.get("makespan")
    if mk is not None:
        if not mk.get("moe_overlap", {}).get("win"):
            raise SystemExit(
                "--check: MoE-dispatch overlap win lost "
                f"({mk.get('moe_overlap')!r})")
        if int(mk.get("strict_wins", 0)) < 1:
            raise SystemExit(
                "--check: pipelined pass no longer beats armed serial "
                "anywhere in the corpus")
        print(f"# makespan: {mk['strict_wins']} overlap wins, "
              f"moe-dispatch p{mk['moe_overlap']['best_parts']} "
              f"{mk['moe_overlap']['speedup']}x", file=sys.stderr)
    # pallas section: launch amortization + fused-epilogue traffic are
    # model-level claims, machine-independent — blocking gates
    pal = data.get("pallas")
    if pal is None:
        raise SystemExit(
            "--check: current run's payload lacks the pallas section")
    bad = {k: v for k, v in pal.get("launches", {}).items()
           if v.get("launches_per_run") != 1 or v.get("jit_traces") != 1}
    if bad or not pal.get("launches"):
        raise SystemExit(
            f"--check: single-kernel launch amortization lost: "
            f"{bad or 'empty corpus'}")
    if not any(v.get("rounds", 0) > 1 for v in pal["launches"].values()):
        raise SystemExit(
            "--check: pallas corpus lost its multi-round schedules "
            "(R -> 1 is vacuous at R == 1)")
    ep = pal.get("epilogue", {})
    if not ep.get("win") or float(ep.get("modeled_win", 0.0)) <= 1.0:
        raise SystemExit(
            f"--check: fused rmsnorm-epilogue win lost ({ep!r})")
    # epilogue walltime stays a trend signal (interpreter time on CPU)
    if float(ep.get("fused_walltime_s", 0.0)) > \
            2.0 * float(ep.get("unfused_walltime_s", 0.0)):
        print(f"::warning::fused epilogue walltime >2x the unfused "
              f"path: {ep['fused_walltime_s']}s vs "
              f"{ep['unfused_walltime_s']}s (interpreter trend)",
              file=sys.stderr)
    rmax = max(v["rounds"] for v in pal["launches"].values())
    print(f"# pallas: {len(pal['launches'])} corpus schedules at 1 "
          f"launch/run (max R={rmax}), epilogue modeled win "
          f"{ep['modeled_win']}x", file=sys.stderr)
    # fleet section: scoped drift healing + elastic re-derivation run on
    # the deterministic model substrate — blocking gates
    fleet = data.get("fleet")
    if fleet is None:
        raise SystemExit(
            "--check: current run's payload lacks the fleet section")
    heal = fleet.get("heal", {})
    if not heal.get("scoped") or not (
            1 <= int(heal.get("cells_retuned", 0))
            <= int(heal.get("cells_affected", 0))
            < int(heal.get("cells_total", 0))):
        raise SystemExit(
            f"--check: drift heal no longer scoped (a beta collapse "
            f"must re-measure some cells but never the whole table): "
            f"{heal!r}")
    if int(heal.get("invalidated", {}).get("executors", 0)) < 1:
        raise SystemExit(
            f"--check: drift heal evicted no stale executors ({heal!r})")
    el = fleet.get("elastic", {})
    if int(el.get("rederived", 0)) < 1 or not el.get("bit_exact"):
        raise SystemExit(
            f"--check: elastic re-derivation lost (schedules must be "
            f"rebuilt bit-exact for the shrunk topology): {el!r}")
    print(f"# fleet: healed {heal['cells_retuned']}/{heal['cells_total']}"
          f" cells (scoped), elastic re-derived {el['rederived']} "
          f"schedules bit-exact", file=sys.stderr)
    # chaos section: seeded fault campaigns on the deterministic sim
    # substrate — every claim machine-independent and blocking
    ch = data.get("chaos")
    if ch is None:
        raise SystemExit(
            "--check: current run's payload lacks the chaos section")
    for campaign, row in sorted(ch.get("campaigns", {}).items()):
        if not row.get("recovered_bitwise"):
            raise SystemExit(
                f"--check: chaos campaign {campaign!r} no longer "
                f"recovers bitwise: {row!r}")
    if len(ch.get("campaigns", {})) < 4:
        raise SystemExit(
            f"--check: chaos section lost campaigns (need corrupt/fail/"
            f"hang/mixed): {sorted(ch.get('campaigns', {}))!r}")
    unrec = ch.get("unrecoverable", {})
    if not unrec.get("typed") or not unrec.get("bounded"):
        raise SystemExit(
            f"--check: persistent faults must end in a typed "
            f"UnrecoverableError after a bounded ladder walk: {unrec!r}")
    pr = ch.get("verify_pricing", {})
    if not (pr.get("off_s") == 0.0
            and 0.0 < float(pr.get("canary_frac", 0))
            < float(pr.get("full_frac", 0))):
        raise SystemExit(
            f"--check: verify pricing ordering lost (off=0 < canary < "
            f"full): {pr!r}")
    print(f"# chaos: {len(ch['campaigns'])} campaigns bitwise-recovered,"
          f" unrecoverable walk bounded at {unrec['attempts']} attempts,"
          f" canary={pr['canary_frac']}x full={pr['full_frac']}x",
          file=sys.stderr)
    # serve section: the continuous-batching trace runs on the seeded
    # sim substrate with an in-engine bitwise oracle — every claim is
    # machine-independent and blocking
    sv = data.get("serve")
    if sv is None:
        raise SystemExit(
            "--check: current run's payload lacks the serve section")
    tr = sv.get("traffic", {})
    if not tr.get("completed") \
            or tr.get("completed") != tr.get("submitted"):
        raise SystemExit(
            f"--check: continuous-batching trace no longer drains "
            f"({tr.get('completed')!r}/{tr.get('submitted')!r} "
            f"requests)")
    if int(tr.get("tenants", 0)) < 2:
        raise SystemExit(
            f"--check: serve trace lost its multi-tenant mix "
            f"(tenants={tr.get('tenants')!r})")
    if not tr.get("bitwise_vs_oracle") \
            or int(tr.get("kv_transfer", {}).get("plans", 0)) < 1:
        raise SystemExit(
            f"--check: KV transfers must move via ragged plans and "
            f"match the gather oracle bitwise: {tr.get('kv_transfer')!r}")
    if float(tr.get("tokens_per_step", 0)) <= 0 \
            or "p99" not in tr.get("ttft_steps", {}):
        raise SystemExit(
            f"--check: serve throughput/TTFT metrics lost "
            f"(tokens_per_step={tr.get('tokens_per_step')!r}, "
            f"ttft={tr.get('ttft_steps')!r})")
    ag = sv.get("aggregation", {})
    sp = ag.get("shared_prefix", {})
    if not ag.get("msgs_win") or not sp.get("bytes_win") \
            or not sp.get("bitwise"):
        raise SystemExit(
            f"--check: locality-aware KV aggregation win lost "
            f"(msgs_win={ag.get('msgs_win')!r}, "
            f"shared_prefix={sp!r})")
    cl = sv.get("chaos_under_load", {})
    if cl.get("completed") != cl.get("submitted") \
            or int(cl.get("degraded_recovered", 0)) < 1 \
            or not cl.get("recovered_bitwise"):
        raise SystemExit(
            f"--check: chaos-under-load serving no longer recovers "
            f"({cl!r})")
    print(f"# serve: {tr['completed']}/{tr['submitted']} requests, "
          f"{tr['kv_transfer']['plans']} ragged plans bitwise, "
          f"shared-prefix dedupe "
          f"{sp['standard_dcn_bytes']}->{sp['locality_dcn_bytes']}B "
          f"dcn, chaos degraded/recovered {cl['degraded_recovered']}",
          file=sys.stderr)


def main(argv=()) -> dict:
    # argv defaults to empty (run.py's bench loop calls main() with no
    # args and must not inherit run.py's own sys.argv flags); the CLI
    # entry below passes sys.argv[1:] explicitly
    argv = list(argv)

    def operand(flag: str) -> str | None:
        if flag not in argv:
            return None
        i = argv.index(flag)
        if i + 1 >= len(argv):
            raise SystemExit(f"{flag} requires a file path")
        return argv[i + 1]

    json_path = operand("--json")
    check_path = operand("--check")
    data = payload()
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(data, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"# wrote transport benchmark to {json_path}",
              file=sys.stderr)
    if check_path:
        check_against(check_path, data)
    return data


if __name__ == "__main__":
    from benchmarks.common import header

    header()
    main(sys.argv[1:])
