"""Serving launcher: batched prefill + greedy decode with a KV cache,
and the continuous-batching traffic-simulator path.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --smoke --batch 4 --prompt-len 32 --gen 16

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --smoke --continuous --arrival-rate 6 --tenants 3 --requests 48
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat, configs
from repro.core import api as mpix_api
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.serve.step import (ServeOptions, jit_decode_step, place,
                              token_spec)
from repro.train.sharding import data_axes


def _run_continuous(args, cfg) -> dict:
    """Continuous batching: drive the engine through a seeded Poisson
    multi-tenant trace; KV blocks move prefill-pool -> decode-pool via
    ragged neighbor plans on ``--kv-transport`` (resilience ladder when
    ``--resilience`` is armed)."""
    from repro.serve.engine import ContinuousBatchingEngine, EngineConfig
    from repro.serve.traffic import poisson_workload, run_workload

    resilience = None
    if args.resilience != "off":
        # lead the ladder with the requested substrate; keep the walk on
        # host rungs so per-batch plans never pay a device compile
        lead = args.kv_transport if args.kv_transport != "reference" \
            else "sim"
        ladder = tuple(dict.fromkeys((lead, "sim", "reference")))
        resilience = {"verify": args.resilience, "ladder": ladder,
                      "backoff_s": 1e-4}
    ecfg = EngineConfig(
        blocks_per_rank=args.kv_blocks,
        block_feat=(getattr(cfg, "head_dim", None) or 16),
        transport=args.kv_transport,
        resilience=resilience,
        policy=args.select_policy)
    engine = ContinuousBatchingEngine(ecfg)
    trace = poisson_workload(args.seed, arrival_rate=args.arrival_rate,
                             tenants=args.tenants,
                             n_requests=args.requests,
                             max_prompt=args.kv_blocks
                             * ecfg.block_tokens // 2)
    t0 = time.time()
    metrics = run_workload(engine, trace)
    dt = time.time() - t0
    kv = metrics["kv_transfer"]
    print(f"continuous: {metrics['completed']}/{metrics['submitted']} "
          f"requests over {args.tenants} tenants in "
          f"{metrics['steps']} steps ({dt:.2f}s), "
          f"{metrics['tokens']} tokens "
          f"({metrics['tokens_per_step']} tok/step, "
          f"{metrics['tokens_per_s']} tok/s)")
    print(f"ttft: mean {metrics['ttft_steps']['mean']} steps, "
          f"p99 {metrics['ttft_steps']['p99']}; "
          f"preemptions {metrics['preemptions']}")
    print(f"kv-transfer: {kv['plans']} plans, {kv['blocks']} blocks, "
          f"{kv['bytes']}B ({kv['dcn_bytes']}B dcn / "
          f"{kv['ici_bytes']}B ici) via {kv['plan_names']}, "
          f"{kv['wall_s']}s wall")
    if metrics["degradations"]:
        print(f"resilience: {metrics['degradations']} degradation "
              f"report(s) collected")
    return metrics


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="local",
                    choices=["local", "single", "multi"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--select-policy", default="model",
                    choices=["fixed", "model", "tuned"],
                    help="algorithm selection policy for algorithm="
                         "'auto' collectives (tuned reads the persisted "
                         "tuner table; see repro.core.tuner)")
    ap.add_argument("--autotune", action="store_true",
                    help="tune this mesh before serving (persists "
                         "winners for --select-policy tuned); an "
                         "existing table is healed in place — only "
                         "guideline-violating cells are re-measured")
    ap.add_argument("--autotune-full", action="store_true",
                    help="ignore any persisted table and re-measure "
                         "everything from scratch (implies --autotune)")
    ap.add_argument("--probe-links", action="store_true",
                    help="wire-measure per-level link models before "
                         "tuning; tables key on measured geometry "
                         "(lm[] fingerprints)")
    ap.add_argument("--heal-interval", type=float, default=0.0,
                    help="run the drift-healing tuner daemon in the "
                         "background every N seconds while serving "
                         "(0 = off); heals are scoped to drifted cells")
    ap.add_argument("--ep-alltoall", default="xla",
                    help="mpix algorithm for the explicit EP dispatch "
                         "(only used when --ep-transport is set)")
    ap.add_argument("--ep-transport", default=None,
                    choices=["shardmap", "pallas", "auto"],
                    help="enable explicit expert-parallel prefill "
                         "dispatch on this substrate: one ppermute per "
                         "round (shardmap), the whole schedule as a "
                         "single device kernel (pallas), or the tuner's "
                         "per-size choice (auto)")
    ap.add_argument("--resilience", default="off",
                    choices=["off", "canary", "full"],
                    help="arm the chaos-recovery ladder on the serve "
                         "collectives: EP dispatch (needs "
                         "--ep-transport) and/or continuous-mode KV "
                         "transfers (--continuous); canary/full set "
                         "the host-level verification mode")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching mode: drive the "
                         "disaggregated prefill/decode engine through "
                         "a seeded Poisson multi-tenant trace; KV "
                         "blocks move between pools via ragged "
                         "neighbor plans")
    ap.add_argument("--arrival-rate", type=float, default=4.0,
                    help="continuous mode: mean requests/sec of the "
                         "Poisson arrival process")
    ap.add_argument("--tenants", type=int, default=2,
                    help="continuous mode: tenant count of the bursty "
                         "traffic mix (each tenant has its own "
                         "prompt/gen length skew)")
    ap.add_argument("--requests", type=int, default=32,
                    help="continuous mode: trace length")
    ap.add_argument("--kv-transport", default="sim",
                    choices=["sim", "reference", "shardmap", "pallas"],
                    help="continuous mode: substrate executing the KV "
                         "block-transfer schedules (shardmap needs one "
                         "device per engine rank)")
    ap.add_argument("--kv-blocks", type=int, default=32,
                    help="continuous mode: KV blocks per engine rank")
    ap.add_argument("--seed", type=int, default=0,
                    help="continuous mode: trace seed")
    args = ap.parse_args(argv)
    compat.enable_compile_cache()

    # ---- argument validation (fail loudly, never deep in the loop) ----
    if args.gen < 1:
        ap.error(f"--gen must be >= 1 (got {args.gen}): generating "
                 f"zero tokens leaves nothing to stack or serve")
    if args.prompt_len < 1:
        ap.error(f"--prompt-len must be >= 1 (got {args.prompt_len})")
    if args.batch < 1:
        ap.error(f"--batch must be >= 1 (got {args.batch})")
    if args.continuous:
        if args.arrival_rate <= 0:
            ap.error(f"--arrival-rate must be > 0 "
                     f"(got {args.arrival_rate})")
        if args.tenants < 1:
            ap.error(f"--tenants must be >= 1 (got {args.tenants})")
        if args.requests < 1:
            ap.error(f"--requests must be >= 1 (got {args.requests})")
    if args.resilience != "off" and args.ep_transport is None \
            and not args.continuous:
        # resilience only threads through the EP dispatch and the KV
        # transfer collectives; without either armed it silently
        # protected nothing — fail loudly instead (satellite bugfix)
        raise SystemExit(
            f"--resilience {args.resilience} has nothing to protect: "
            f"the single-shot decode path runs no mpix collectives. "
            f"Arm a protected path with --ep-transport "
            f"shardmap|pallas|auto (EP dispatch) or --continuous "
            f"(KV-cache transfers), or drop --resilience.")

    mpix_api.set_default_policy(args.select_policy)
    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.mesh == "local":
        n = jax.device_count()
        mesh = compat.make_mesh((n, 1), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    if args.autotune or args.autotune_full:
        from repro.launch.train import autotune_mesh
        autotune_mesh(mesh, full=args.autotune_full,
                      probe=args.probe_links)
    daemons = []
    if args.heal_interval > 0:
        from repro.launch.train import heal_daemons
        daemons = heal_daemons(mesh, 1)
        for d in daemons:
            d.start(interval_s=args.heal_interval)

    # daemons must stop even when the serve body raises (leak fix):
    # same pattern train's FaultTolerantLoop uses for signal handlers
    try:
        max_len = args.prompt_len + args.gen
        with jax.set_mesh(mesh):
            if args.continuous:
                return _run_continuous(args, cfg)

            params = M.init_params(jax.random.key(0), cfg)
            prompts = jax.random.randint(
                jax.random.key(1), (args.batch, args.prompt_len), 2,
                cfg.vocab_size)
            cross = None
            if cfg.encoder is not None:
                frames = jax.random.normal(
                    jax.random.key(2),
                    (args.batch, cfg.encoder.n_frames,
                     cfg.encoder.d_model),
                    jnp.bfloat16)
                cross = M.encode(params, cfg, frames)

            cache = M.init_cache(cfg, args.batch, max_len)
            ep_options = None
            if args.ep_transport is not None:
                from repro.train.moe_dispatch import EPOptions
                ep_options = EPOptions(alltoall=args.ep_alltoall,
                                       transport=args.ep_transport,
                                       policy=args.select_policy)
            opts = ServeOptions(
                ep_options=ep_options,
                resilience=(None if args.resilience == "off"
                            else args.resilience))
            # jit through jit_decode_step so params/cache carry their
            # NamedShardings — a bare jax.jit silently replicated the
            # cache on multi-device meshes (satellite bugfix)
            decode, (pspec, cspec) = jit_decode_step(
                cfg, mesh, opts, params, cache)
            params = place(mesh, params, pspec)
            cache = place(mesh, cache, cspec)
            tspec = token_spec(mesh, opts)
            if cross is not None:
                cross = place(mesh, cross, P(data_axes(mesh)))

            # prefill token-by-token through the decode step (keeps one
            # compiled program; the batched-prefill path is exercised by
            # the dry-run and benches)
            t0 = time.time()
            tok = place(mesh, prompts[:, :1], tspec)
            outs = []
            for i in range(max_len - 1):
                a = (params, cache, tok) if cfg.encoder is None else \
                    (params, cache, tok, cross)
                nxt, cache = decode(*a)
                if i + 1 < args.prompt_len:
                    tok = place(mesh, prompts[:, i + 1: i + 2], tspec)
                else:
                    tok = nxt
                    outs.append(np.asarray(nxt)[:, 0])
            dt = time.time() - t0
    finally:
        for d in daemons:
            d.stop()
            healed = sum(1 for r in d.reports if r.healed)
            if healed:
                print(f"tuner daemon: {len(d.reports)} probe pass(es), "
                      f"{healed} heal(s) on {d.topo.fingerprint()}")
    gen = (np.stack(outs, 1) if outs
           else np.zeros((args.batch, 0), np.int32))
    print(f"generated {gen.shape} in {dt:.2f}s "
          f"({(max_len - 1) * args.batch / dt:.1f} tok/s)")
    print(gen[:, :12])
    return gen


if __name__ == "__main__":
    main()
