"""Training launcher: data pipeline + train step + fault-tolerant loop.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --smoke --steps 50 --batch 4 --seq 64 --ckpt-dir /tmp/run1

Defaults run the reduced (smoke) config on the local devices; the same
flags drive the production mesh on a real pod (--mesh single|multi —
requires the matching device count).  Restart the same command after a
crash/preemption: it resumes from the newest committed checkpoint, on
the current mesh (elastic).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import compat, configs
from repro.core import api as mpix_api
from repro.data import DataPipeline, PipelineConfig
from repro.launch.mesh import make_production_mesh
from repro.runtime import FaultTolerantLoop, PreemptionSignal
from repro.train.step import (TrainOptions, init_train_state,
                              make_train_step)


def build(args):
    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.mesh == "local":
        n = jax.device_count()
        mesh = compat.make_mesh((n, 1), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        from repro.models.common import set_shard_mesh
        set_shard_mesh(mesh)
    opts = TrainOptions(
        dp_mode=args.dp_mode, dp_algorithm=args.dp_algorithm,
        grad_buckets=args.grad_buckets, moe_mode=args.moe_mode,
        ep_alltoall=args.ep_alltoall, ep_policy=args.select_policy,
        ep_transport=args.ep_transport, dp_transport=args.dp_transport,
        resilience=(None if args.resilience == "off"
                    else args.resilience),
        remat=not args.smoke,
        peak_lr=args.lr, warmup_steps=max(1, args.steps // 20),
        total_steps=args.steps)
    return cfg, mesh, opts


def mesh_topologies(mesh):
    """The topologies runtime collectives actually query on this mesh.

    A tuned-policy lookup keys on the topology of the *axis subset* a
    collective runs over (``api.topology_from_axes``), not the whole
    mesh: dp sync uses ("pod","data")/("data",), MoE EP uses
    ("pod","model")/("model",), the token rebuild uses ("model",).  So
    tune one topology per single non-DCN axis plus one per ("pod",
    axis) pair, deduped — a whole-mesh-only table would never be hit.
    """
    from repro.core.topology import Topology, flat_topology
    topos = {}
    names = [a for a in mesh.axis_names if a != "pod"]
    npods = mesh.shape.get("pod", 1) if "pod" in mesh.axis_names else 1
    for a in names:
        size = mesh.shape[a]
        if size > 1:
            t = flat_topology(size)
            topos[t.fingerprint()] = t
            if npods > 1:
                t = Topology(nranks=npods * size, ranks_per_pod=size)
                topos[t.fingerprint()] = t
    if not topos:
        t = flat_topology(mesh.devices.size)
        topos[t.fingerprint()] = t
    return list(topos.values())


def autotune_mesh(mesh, repeats: int = 3, full: bool = False,
                  probe: bool = False):
    """Tune (or heal) every topology this mesh's collectives query at
    trace time.

    A topology with no persisted table gets a full ``tuner.autotune``
    (measures every path — dense collectives, neighbor aggregate-vs-
    standard, partitioned chunking — and persists winners).  A topology
    that already has a table is *healed* instead (``tuner.heal_table``):
    guideline violations and cells missing newly registered algorithms
    trigger a scoped re-measure of only those cells and bump the table
    generation — untouched cells keep their timings.  ``full=True``
    forces a from-scratch re-tune of everything.

    ``probe=True`` runs the wire-measurement pass first
    (``core.linkprobe``): each topology's per-level alpha/beta is
    measured through the transports (ping-pong/injection probes) and
    the tables are keyed by the *measured* geometry — their
    fingerprints carry the fitted ``lm[...]`` link models instead of
    datasheet constants.
    """
    from repro.core import linkprobe, tuner
    tables = []
    for topo in mesh_topologies(mesh):
        if probe:
            measured = linkprobe.measured_topology(topo, repeats=repeats)
            print(f"probed links: {topo.fingerprint()} -> "
                  f"{tuner.substrate_fingerprint(measured)}")
            topo = measured
        table = (None if full else
                 tuner.load_table(tuner.substrate_fingerprint(topo)))
        if table is None:
            table = tuner.autotune(topo, repeats=repeats)
            print(f"autotuned {table.fingerprint} ({table.source}): "
                  f"{sorted(table.entries)}")
        else:
            healed = tuner.heal_table(table, topo, repeats=repeats)
            print(f"reused {table.fingerprint} ({table.source}, "
                  f"generation {table.generation}): "
                  f"{len(healed)} cell(s) repaired")
        for v in table.violations:
            print(f"  guideline violation: {v}")
        tables.append(table)
    return tables


def heal_daemons(mesh, heal_every: int):
    """One ``TuningDaemon`` per mesh topology, probing every
    ``heal_every`` steps — the online drift-healing heartbeat the
    training loop ticks from ``on_step``."""
    from repro.runtime import TuningDaemon
    return [TuningDaemon(topo, probe_every=heal_every)
            for topo in mesh_topologies(mesh)]


def make_elastic(mesh, policy: str):
    """(RankLossSignal, on_rank_loss) for ``FaultTolerantLoop``: on
    rank loss, re-derive the launcher's staged schedules (grad sync +
    EP dispatch) for the shrunk topology and swap them in place — the
    loop keeps stepping, no restart."""
    from repro.core import selector
    from repro.runtime import ElasticScheduleSet, RankLossSignal

    topo = max(mesh_topologies(mesh), key=lambda t: t.nranks)
    nbytes = 1 << 20
    entries = {}
    for name, coll in (("grad_sync", "allreduce"),
                       ("ep_dispatch", "alltoall")):
        algo = selector.select(coll, topo, nbytes, policy=policy)
        if algo == "xla":          # schedule sets hold IR plans only
            algo = selector.select(coll, topo, nbytes, policy="model")
        entries[name] = (coll, algo)
    schedules = ElasticScheduleSet(topo, entries)
    signal = RankLossSignal()

    def on_rank_loss(state, step, lost):
        in_range = [r for r in lost if r < schedules.topo.nranks]
        if not in_range or len(in_range) >= schedules.topo.nranks:
            print(f"rank loss {lost} outside schedule topology; "
                  f"no swap")
            return None
        rep = schedules.shrink(in_range)
        print(f"elastic swap @step {step}: lost {rep.lost_ranks}, "
              f"{rep.old_fingerprint} -> {rep.new_fingerprint}, "
              f"re-derived {len(rep.rederived)} schedule(s), evicted "
              f"{rep.invalidated} stale executor(s)", flush=True)
        return None                # state/step_fn unchanged: swap only

    return signal, on_rank_loss, schedules


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--mesh", default="local",
                    choices=["local", "single", "multi"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--dp-mode", default="fsdp")
    ap.add_argument("--dp-algorithm", default="xla")
    ap.add_argument("--select-policy", default="model",
                    choices=["fixed", "model", "tuned"],
                    help="algorithm selection policy for algorithm="
                         "'auto' collectives (tuned reads the persisted "
                         "tuner table; see repro.core.tuner)")
    ap.add_argument("--autotune", action="store_true",
                    help="tune this mesh before training (persists dense "
                         "+ neighbor + partitioned winners for "
                         "--select-policy tuned); an existing table is "
                         "healed in place — only guideline-violating "
                         "cells are re-measured")
    ap.add_argument("--autotune-full", action="store_true",
                    help="ignore any persisted table and re-measure "
                         "everything from scratch (implies --autotune)")
    ap.add_argument("--probe-links", action="store_true",
                    help="wire-measure per-level link models before "
                         "tuning (ping-pong/injection probes through "
                         "the transports); tuned tables key on the "
                         "measured geometry (lm[] fingerprints)")
    ap.add_argument("--heal-every", type=int, default=0,
                    help="re-probe the fabric every N steps and heal "
                         "tuned tables on drift — scoped: only cells "
                         "whose selection the drift can move are "
                         "re-measured (0 = off)")
    ap.add_argument("--elastic", action="store_true",
                    help="on rank loss (RankLossSignal), re-derive the "
                         "staged schedules for the shrunk topology and "
                         "swap executors in place instead of exiting")
    ap.add_argument("--grad-buckets", type=int, default=1)
    ap.add_argument("--moe-mode", default="dropless")
    ap.add_argument("--ep-alltoall", default="xla")
    ap.add_argument("--ep-transport", default="shardmap",
                    choices=["shardmap", "pallas", "auto"],
                    help="substrate for schedule-backed EP collectives: "
                         "one ppermute per round (shardmap), the whole "
                         "schedule as a single device kernel (pallas), "
                         "or the tuner's per-size choice (auto)")
    ap.add_argument("--dp-transport", default="shardmap",
                    choices=["shardmap", "pallas", "auto"],
                    help="substrate for explicit-mode gradient sync "
                         "(same choices as --ep-transport)")
    ap.add_argument("--resilience", default="off",
                    choices=["off", "canary", "full"],
                    help="chaos-resilient collectives: arm the recovery "
                         "ladder (retry + transport fallback + "
                         "algorithm refit + xla) for EP dispatch and "
                         "explicit-mode grad sync; canary/full set the "
                         "host-level verification mode")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    compat.enable_compile_cache()

    mpix_api.set_default_policy(args.select_policy)
    cfg, mesh, opts = build(args)
    if args.autotune or args.autotune_full:
        autotune_mesh(mesh, full=args.autotune_full,
                      probe=args.probe_links)
    daemons = heal_daemons(mesh, args.heal_every) if args.heal_every \
        else []
    pipe = DataPipeline(PipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch))
    with jax.set_mesh(mesh):
        step_fn = jax.jit(make_train_step(cfg, mesh, opts))
        state = init_train_state(jax.random.key(0), cfg, opts)

        losses = []
        t_last = [time.time()]

        def one_step(state, step):
            batch = pipe.batch(step)
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
            if (step + 1) % args.log_every == 0:
                dt = (time.time() - t_last[0]) / args.log_every
                t_last[0] = time.time()
                print(f"step {step+1:5d}  loss {losses[-1]:.4f}  "
                      f"lr {float(metrics['lr']):.2e}  "
                      f"{dt*1e3:.0f} ms/step", flush=True)
            return state

        def on_step(step, state):
            for d in daemons:
                rep = d.tick(step)
                if rep is not None and rep.healed:
                    print(f"drift healed @step {step}: levels "
                          f"{rep.drifted_levels}, re-measured "
                          f"{len(rep.retuned_cells)}/{rep.total_cells} "
                          f"cell(s), generation {rep.generation}",
                          flush=True)

        if args.ckpt_dir:
            rank_loss = on_rank_loss = None
            if args.elastic:
                rank_loss, on_rank_loss, _ = make_elastic(
                    mesh, args.select_policy)
            loop = FaultTolerantLoop(args.ckpt_dir,
                                     ckpt_every=args.ckpt_every,
                                     preemption=PreemptionSignal(True),
                                     rank_loss=rank_loss,
                                     on_rank_loss=on_rank_loss)
            state, start = loop.resume_or_init(state)
            if start:
                print(f"resumed from step {start}")
            state, stopped = loop.run(state, one_step,
                                      start_step=start,
                                      num_steps=args.steps - start,
                                      on_step=on_step if daemons
                                      else None)
        else:
            for s in range(args.steps):
                state = one_step(state, s)
                on_step(s + 1, state)

    if losses:
        print(f"final loss {np.mean(losses[-5:]):.4f} "
              f"(first {np.mean(losses[:5]):.4f})")
    else:
        print("nothing to do (already past --steps; checkpoint is "
              "complete)")
    return losses


if __name__ == "__main__":
    main()
