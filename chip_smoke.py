"""Run the main path once on TPU chips and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the paths that exist only across chips

One chip: the Pallas transport kernel for every registered schedule and a
KV-transfer plan, bitwise against ``SimTransport.run_reference`` and
``kvtransfer.gather_oracle``; the compute kernels at smollm-360m width
against their references; ``repro.launch.train`` for 4 steps and
``repro.launch.serve`` (batched decode, then continuous batching with KV
transfers on the pallas transport), all at full smollm-360m width.

Four chips: the explicit-DP train step with ``auto`` against ``xla``
gradient sync; the sharded decode step; every ``mpix_*`` collective
inside ``shard_map`` on a flat 4-chip mesh and a 2x2 (pod, data) mesh,
bitwise against ``SimTransport.run_reference`` and close to
``algorithm="xla"`` (every registered algorithm on both transports at 4
KiB and 1 MiB per rank; the model policy's pick on shardmap at 64 MiB).

Every phase prints one line with what ran, its seconds and its compile
seconds.  The last line of standard output is one JSON object naming the
device.  The script exits non-zero and prints no result when JAX finds no
TPU, when the repo's ``src/`` is not next to it, or when a phase fails.
It runs everything in this one process and starts no other.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "smollm-360m"
KiB, MiB = 1 << 10, 1 << 20

_COMPILE_S = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event.endswith("backend_compile_duration"):
        _COMPILE_S[0] += duration


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _same_bits(a, b) -> bool:
    """Bitwise equality (``-0.0`` and NaN payloads cannot hide)."""
    a, b = np.ascontiguousarray(a), np.ascontiguousarray(b)
    return (a.shape == b.shape and a.dtype == b.dtype
            and np.array_equal(a.view(np.uint8), b.view(np.uint8)))


def _normal(rng, shape, dtype):
    return rng.standard_normal(shape, dtype=np.float32).astype(dtype)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def transport_kernels(sizes=(8 * KiB, 4 * MiB)) -> str:
    """``PallasTransport(4).run_global`` for every registered schedule on
    a flat and a 2-pod 4-rank topology, f32 and bf16, at each global
    buffer size, bitwise against ``SimTransport.run_reference``; plus
    KV-transfer plans on the pallas transport against the gather
    oracle."""
    import jax.numpy as jnp

    from repro.core import kvtransfer
    from repro.core.algorithms import REGISTRY
    from repro.core.pallas_lowering import get_pallas_exec
    from repro.core.schedule import NotApplicable
    from repro.core.topology import Topology, flat_topology
    from repro.core.transport import PallasTransport, SimTransport

    rng = np.random.default_rng(0)
    runs = 0
    for topo in (flat_topology(4), Topology(4, 2)):
        pt, sim = PallasTransport(4, topo=topo), SimTransport(4)
        seen = set()
        for coll, algos in REGISTRY.items():
            for name, build in algos.items():
                try:
                    sched = build(topo)
                except NotApplicable:
                    continue
                if sched.fingerprint() in seen:
                    continue
                seen.add(sched.fingerprint())
                pex = get_pallas_exec(sched, topo=topo)
                _check(not pex.interpret, f"{name} would be interpreted")
                for dtype in (np.float32, jnp.bfloat16):
                    size = np.dtype(dtype).itemsize
                    for gbytes in sizes:
                        elems = max(1, gbytes // (4 * sched.num_slots * size))
                        buf = _normal(rng, (4, sched.num_slots, elems), dtype)
                        want = sim.run_reference(sched, buf)
                        got = pt.run_global(sched, buf)
                        what = (f"{topo.fingerprint()} {coll}.{name} "
                                f"{np.dtype(dtype).name} {gbytes}B")
                        _check(_same_bits(got, want),
                               f"{what}: pallas != run_reference")
                        _check("tpu_custom_call" in pex.lower(
                            buf.shape, buf.dtype).as_text(),
                            f"{what}: no Mosaic kernel in the program")
                        runs += 1
    # KV-transfer plans (8-rank engine topology, one kernel each)
    topo, B = Topology(8, 4), 32
    pool = _normal(rng, (8, B, 8, 64), np.float32)
    moves, seen = [], set()
    for s in range(4):
        for j in range(3):
            m = kvtransfer.BlockMove(s, (s + j) % B, 4 + (s + j) % 4,
                                     (2 * s + j) % B)
            if (m.dst, m.dst_row) not in seen:
                seen.add((m.dst, m.dst_row))
                moves.append(m)
    for aggregate in (False, True):
        tp = kvtransfer.build_transfer_plan(
            moves, topo, blocks_per_rank=B, aggregate=aggregate,
            block_bytes=8 * 64 * 4)
        res = kvtransfer.run_transfer(tp, pool, transport="pallas")
        _check(kvtransfer.verify_bitwise(tp, pool, res),
               f"kv transfer aggregate={aggregate}: pallas != oracle")
    return (f"{runs} schedule runs bitwise == run_reference, "
            f"2 kv-transfer plans bitwise == gather_oracle")


def compute_kernels(tokens: int = 16384, batch: int = 4,
                    seq: int = 2048) -> str:
    """rmsnorm, rmsnorm_allreduce and flash_attention (plain and with
    the ``q_rows`` gather prologue) at smollm-360m widths against their
    references."""
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.kernels.attention.ops import (flash_attention,
                                             gathered_attention_ref)
    from repro.kernels.attention.ref import attention_ref
    from repro.kernels.rmsnorm.ops import (rmsnorm, rmsnorm_allreduce,
                                           rmsnorm_allreduce_ref)
    from repro.kernels.rmsnorm.ref import rmsnorm_ref

    cfg = configs.get_config(ARCH)
    d, a = cfg.d_model, cfg.attn
    rng = np.random.default_rng(1)
    out = []

    def close(name, got, want, tol):
        err = float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32)
                                    - jnp.asarray(want, jnp.float32))))
        _check(np.isfinite(err) and err <= tol,
               f"{name}: max |err| {err} > {tol}")
        out.append(f"{name} max|err|={err:.3g}<={tol}")

    x = jnp.asarray(_normal(rng, (tokens, d), jnp.bfloat16))
    scale = jnp.asarray(1.0 + 0.1 * rng.standard_normal(d), jnp.float32)
    close("rmsnorm", rmsnorm(x, scale), rmsnorm_ref(x, scale), 0.1)
    parts = jnp.asarray(_normal(rng, (4, tokens, d), jnp.bfloat16))
    close("rmsnorm_allreduce", rmsnorm_allreduce(parts, scale),
          rmsnorm_allreduce_ref(parts, scale), 0.1)

    q = jnp.asarray(_normal(rng, (batch, seq, a.n_heads, a.head_dim),
                            jnp.bfloat16))
    k, v = (jnp.asarray(_normal(rng, (batch, seq, a.n_kv_heads,
                                      a.head_dim), jnp.bfloat16))
            for _ in range(2))
    plain = flash_attention(q, k, v, causal=True)
    close("flash_attention", plain, attention_ref(q, k, v, causal=True),
          0.02)
    ident = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32),
                             (batch, seq))
    _check(_same_bits(flash_attention(q, k, v, causal=True, q_rows=ident),
                      plain), "flash_attention q_rows=identity != plain")
    rows = np.stack([rng.permutation(seq) for _ in range(batch)])
    rows[:, ::7] = -1                              # dropped dispatch slots
    rows = jnp.asarray(rows, jnp.int32)
    got = flash_attention(q, k, v, causal=True, q_rows=rows)
    _check(bool((np.asarray(got)[np.asarray(rows) < 0] == 0).all()),
           "flash_attention q_rows: dead rows are not zero")
    close("flash_attention(q_rows)", got,
          gathered_attention_ref(q, k, v, rows, causal=True), 0.02)
    jax.block_until_ready(got)
    return "; ".join(out) + "; q_rows=identity bitwise == plain"


def train_one_chip(seq: int = 1024, smoke: bool = False) -> str:
    """``repro.launch.train.main`` for 4 explicit-DP steps with
    ``--dp-algorithm auto``; the losses must be finite."""
    from repro.launch import train

    argv = ["--arch", ARCH, "--steps", "4", "--batch", "8",
            "--seq", str(seq), "--dp-mode", "explicit",
            "--dp-algorithm", "auto", "--select-policy", "model",
            "--log-every", "1"] + (["--smoke"] if smoke else [])
    losses = train.main(argv)
    _check(len(losses) == 4 and bool(np.all(np.isfinite(losses))),
           f"train losses {losses}")
    return f"4 steps batch 8 seq {seq}, losses {losses}"


def serve_one_chip(requests: int = 16, smoke: bool = False) -> str:
    """``repro.launch.serve.main``: batched greedy decode (batch 4,
    prompt 32, gen 8), then continuous batching whose KV blocks move on
    the pallas transport."""
    from repro import configs
    from repro.launch import serve

    base = ["--arch", ARCH, "--select-policy", "model"] + (
        ["--smoke"] if smoke else [])
    vocab = (configs.get_smoke(ARCH) if smoke
             else configs.get_config(ARCH)).vocab_size
    gen = serve.main(base + ["--batch", "4", "--prompt-len", "32",
                             "--gen", "8"])
    _check(gen.shape == (4, 8) and bool(((gen >= 0) & (gen < vocab)).all()),
           f"decode produced {gen.shape} tokens {gen}")
    m = serve.main(base + ["--continuous", "--kv-transport", "pallas",
                           "--requests", str(requests)])
    _check(m["completed"] == m["submitted"] == requests
           and m["kv_transfer"]["plans"] >= 1,
           f"continuous: {m['completed']}/{m['submitted']} completed")
    return (f"decode {gen.shape[0]}x{gen.shape[1]} tokens; continuous "
            f"{m['completed']}/{m['submitted']} requests, "
            f"{m['kv_transfer']['plans']} pallas kv plans")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def _layout(coll: str, sched, xr: np.ndarray, n: int) -> np.ndarray:
    """The global [n, slots, ...] buffer the ``mpix_*`` call builds from
    per-rank inputs ``xr`` [n, rows, f]."""
    rows = xr.shape[1]
    if coll == "allreduce":
        return xr.reshape(n, n, -1)
    if coll == "allgather":
        g = np.zeros((n, n) + xr.shape[1:], xr.dtype)
        g[np.arange(n), np.arange(n)] = xr
        return g
    blocks = xr.reshape((n, n, rows // n) + xr.shape[2:])
    if coll == "alltoall" and sched.num_slots > n:
        pad = np.zeros((n, sched.num_slots - n) + blocks.shape[2:],
                       xr.dtype)
        blocks = np.concatenate([blocks, pad], axis=1)
    return blocks


def _expected(coll: str, sched, out: np.ndarray, xr: np.ndarray,
              n: int) -> np.ndarray:
    """Each rank's ``mpix_*`` output from the reference's global
    result, stacked [n, ...]."""
    if coll == "allreduce":
        return out.reshape(xr.shape)
    if coll == "allgather":
        return out.reshape((n, n * xr.shape[1]) + xr.shape[2:])
    if coll == "reduce_scatter":
        return out[np.arange(n), np.arange(n)]
    return out[:, : sched.result_blocks].reshape(xr.shape)


def collectives(sizes=(4 * KiB, 1 * MiB), big: int = 64 * MiB,
                feat: int = 256) -> str:
    """Every mpix_* collective inside shard_map on a flat 4-chip mesh and
    a 2x2 (pod, data) mesh, bitwise against ``SimTransport.run_reference``
    and close to ``algorithm="xla"``.  At each of ``sizes`` per rank:
    every registered algorithm on the shardmap and pallas transports,
    and both kinds of neighbourhood alltoallv plan.  At ``big`` per rank:
    for each dense collective the algorithm the model policy selects at
    that size, on the shardmap transport only (the host references of
    every algorithm on both transports at that size would outlast the
    run)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.core import api, selector
    from repro.core.algorithms import REGISTRY
    from repro.core.schedule import NotApplicable
    from repro.core.topology import Topology
    from repro.core.transport import SimTransport

    n = 4
    meshes = {"data4": (compat.make_mesh((4,), ("data",)), ("data",),
                        Topology(4, 4)),
              "pod2xdata2": (compat.make_mesh((2, 2), ("pod", "data")),
                             ("pod", "data"), Topology(4, 2))}
    fns = {"allreduce": api.mpix_allreduce,
           "allgather": api.mpix_allgather,
           "reduce_scatter": api.mpix_reduce_scatter,
           "alltoall": api.mpix_alltoall}
    rng = np.random.default_rng(2)
    runs, lines = 0, []
    for mname, (mesh, names, topo) in meshes.items():
        sim = SimTransport(n)

        def spmd(fn):
            return jax.jit(compat.shard_map(
                fn, mesh=mesh, in_specs=P(names), out_specs=P(names)))

        devsets = set()
        for nbytes in sizes + (big,):
            full = nbytes != big
            t0, c0, runs0 = time.perf_counter(), _COMPILE_S[0], runs
            rows = max(n, nbytes // (4 * feat))
            xr = _normal(rng, (n, rows, feat), np.float32)
            with jax.set_mesh(mesh):
                x = jax.device_put(xr.reshape(n * rows, feat),
                                   jax.sharding.NamedSharding(mesh,
                                                              P(names)))
            devsets.add(len(x.sharding.device_set))
            for coll, fn in fns.items():
                with jax.set_mesh(mesh):
                    ref_xla = spmd(
                        lambda v, fn=fn: fn(v, names, algorithm="xla"))(x)
                algos = (list(REGISTRY[coll]) if full else
                         [selector.select(coll, topo, nbytes,
                                          policy="model")])
                for algo in algos:
                    try:
                        sched = REGISTRY[coll][algo](topo)
                    except NotApplicable:
                        continue
                    want = _expected(coll, sched, sim.run_reference(
                        sched, _layout(coll, sched, xr, n)), xr, n)
                    for transport in (("shardmap", "pallas") if full
                                      else ("shardmap",)):
                        with jax.set_mesh(mesh):
                            got = spmd(lambda v, fn=fn, a=algo, t=transport:
                                       fn(v, names, algorithm=a,
                                          transport=t))(x)
                            # on the device: no host copy of either side
                            near = bool(jnp.allclose(got, ref_xla,
                                                     rtol=1e-5, atol=1e-4))
                        devsets.add(len(got.sharding.device_set))
                        what = f"{mname} {coll}.{algo} {transport} {nbytes}B"
                        _check(_same_bits(np.asarray(got).reshape(
                            want.shape), want), f"{what}: != run_reference")
                        _check(near, f"{what}: far from algorithm='xla'")
                        del got
                        runs += 1
                    del want
                del ref_xla
            del x
            if full:
                runs += _neighbor(mesh, names, topo, rows * feat, rng,
                                  f"{mname} {nbytes}B")
            # progress, so a run cut short still shows what passed
            print(f"  collectives {mname} {nbytes}B: {runs - runs0} runs "
                  f"bitwise seconds={time.perf_counter() - t0:.3f} "
                  f"compile_seconds={_COMPILE_S[0] - c0:.3f}", flush=True)
        lines.append(f"{mname}: arrays on {sorted(devsets)} devices")
    return f"{runs} collective runs bitwise == run_reference; " + \
        "; ".join(lines)


def _neighbor(mesh, names, topo, elems: int, rng, what: str) -> int:
    """``mpix_neighbor_alltoallv`` on one random graph, both plan kinds,
    both transports, bitwise against the plan's simulator and
    ``run_reference``; returns the runs made."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.core import api
    from repro.core.plan import CommGraph, build_plan, run_sim
    from repro.core.transport import SimTransport

    n, runs = topo.nranks, 0
    graph = CommGraph.random(n, n_local=8, degree=3, rng=rng, dup_frac=0.5)
    vals = _normal(rng, (n, 8, max(1, elems // 8)), np.float32)
    for aggregate in (False, True):
        plan = build_plan(graph, topo, aggregate=aggregate)
        want = run_sim(plan, list(vals))
        ref = SimTransport(n).run_reference(
            plan.schedule, np.concatenate(
                [vals, np.zeros((n, plan.buf_rows - 8) + vals.shape[2:],
                                vals.dtype)], 1))
        for transport in ("shardmap", "pallas"):
            with jax.set_mesh(mesh):
                got = np.asarray(jax.jit(compat.shard_map(
                    lambda v, p=plan, t=transport:
                    api.mpix_neighbor_alltoallv(v, names, p, transport=t),
                    mesh=mesh, in_specs=P(names), out_specs=P(names)))(
                    vals.reshape(n * 8, -1)))
            got = got.reshape(n, -1, vals.shape[2])
            for r in range(n):
                lo, sz = plan.recv_offsets[r], plan.recv_sizes[r]
                _check(_same_bits(got[r, :sz], ref[r, lo:lo + sz])
                       and _same_bits(got[r, :sz], want[r]),
                       f"{what} neighbor agg={aggregate} {transport} "
                       f"rank {r}")
            runs += 1
    return runs


def train_four_chips(seq: int = 1024, smoke: bool = False,
                     steps: int = 3) -> str:
    """The explicit-DP train step of smollm-360m on data=4: ``auto``
    against ``xla`` gradient sync, losses within a tolerance."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import compat, configs
    from repro.data import DataPipeline, PipelineConfig
    from repro.train.step import (TrainOptions, init_train_state,
                                  make_train_step)

    cfg = configs.get_smoke(ARCH) if smoke else configs.get_config(ARCH)
    mesh = compat.make_mesh((4, 1), ("data", "model"))
    pipe = DataPipeline(PipelineConfig(vocab_size=cfg.vocab_size,
                                       seq_len=seq, global_batch=8))
    losses, devsets = {}, {}
    for algo in ("auto", "xla"):
        opts = TrainOptions(dp_mode="explicit", dp_algorithm=algo,
                            remat=not smoke, peak_lr=3e-3, warmup_steps=1,
                            total_steps=steps)
        with jax.set_mesh(mesh):
            step = jax.jit(make_train_step(cfg, mesh, opts))
            state = jax.device_put(
                init_train_state(jax.random.key(0), cfg, opts),
                NamedSharding(mesh, P()))
            losses[algo] = []
            for s in range(steps):
                batch = jax.device_put(pipe.batch(s),
                                       NamedSharding(mesh, P("data")))
                state, metrics = step(state, batch)
                losses[algo].append(float(metrics["loss"]))
        leaf = jax.tree.leaves(state["params"])[0]
        devsets[algo] = (len(leaf.sharding.device_set),
                         len(batch["tokens"].sharding.device_set))
        del state, leaf, batch, metrics      # one state on the chips
    a, x = np.asarray(losses["auto"]), np.asarray(losses["xla"])
    _check(bool(np.all(np.isfinite(a))) and np.allclose(a, x, rtol=1e-3,
                                                         atol=1e-3),
           f"explicit-DP losses auto {a} vs xla {x}")
    return (f"{steps} steps data=4 seq {seq}: auto {a.tolist()} vs xla "
            f"{x.tolist()}; (params, batch) on {devsets['auto']} devices")


def serve_four_chips(smoke: bool = False) -> str:
    """``jit_decode_step`` on a (4, 1) mesh: KV cache leaves partitioned
    over all four chips, a few greedy tokens."""
    import jax
    import jax.numpy as jnp

    from repro import compat, configs
    from repro.models import model as M
    from repro.serve.step import (ServeOptions, jit_decode_step, place,
                                  token_spec)

    cfg = configs.get_smoke(ARCH) if smoke else configs.get_config(ARCH)
    mesh = compat.make_mesh((4, 1), ("data", "model"))
    opts = ServeOptions()
    with jax.set_mesh(mesh):
        params = M.init_params(jax.random.key(0), cfg)
        cache = M.init_cache(cfg, 4, 16)
        decode, (pspec, cspec) = jit_decode_step(cfg, mesh, opts, params,
                                                 cache)
        params, cache = place(mesh, params, pspec), place(mesh, cache, cspec)
        tok = place(mesh, jnp.full((4, 1), 2, jnp.int32),
                    token_spec(mesh, opts))
        toks = []
        for _ in range(8):
            tok, cache = decode(params, cache, tok)
            toks.append(np.asarray(tok)[:, 0])
    sets = [len(leaf.sharding.device_set) for leaf in jax.tree.leaves(cache)]
    _check(all(s == 4 for s in sets), f"cache leaves on {sets} devices")
    _check(any(not leaf.sharding.is_fully_replicated
               for leaf in jax.tree.leaves(cache)),
           "no cache leaf is partitioned")
    gen = np.stack(toks, 1)
    _check(bool(((gen >= 0) & (gen < cfg.vocab_size)).all()),
           f"decode tokens {gen}")
    return (f"{len(sets)} cache leaves each on {sorted(set(sets))} devices; "
            f"decoded {gen.shape[0]}x{gen.shape[1]} tokens")


# ---------------------------------------------------------------------------


def _phase(name: str, fn) -> None:
    t0, c0 = time.perf_counter(), _COMPILE_S[0]
    detail = fn()
    print(f"phase {name}: {detail} seconds={time.perf_counter() - t0:.3f} "
          f"compile_seconds={_COMPILE_S[0] - c0:.3f}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: kernels, train and serve on one chip; 4: the "
                         "collectives, train and serve paths across four "
                         "chips, and nothing else")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"no TPU found: JAX's first device is on "
              f"{devs[0].platform!r}")
    if len(devs) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} TPU devices, "
              f"JAX sees {len(devs)}")
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"the repo's src/repro is not next to {Path(__file__).name}")
    sys.path.insert(0, str(ROOT / "src"))

    from repro import compat
    from repro.core import api
    from repro.kernels.compat import pallas_interpret

    if pallas_interpret():
        _fail("Pallas kernels would run under the interpreter")
    cache = compat.enable_compile_cache()
    api.set_default_policy("model")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"device: {devs[0].device_kind} x{len(devs)}, jax "
          f"{jax.__version__}, compile cache {cache}", flush=True)

    if args.chips == 1:
        phases = [("transport_kernels", transport_kernels),
                  ("compute_kernels", compute_kernels),
                  ("train", train_one_chip),
                  ("serve", serve_one_chip)]
    else:
        phases = [("train_dp4", train_four_chips),
                  ("serve_mesh4x1", serve_four_chips),
                  ("collectives", collectives)]
    for name, fn in phases:
        _phase(name, fn)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
