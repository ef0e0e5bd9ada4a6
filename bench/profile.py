"""Profile one benchmark cell on the chip by the program's named scopes.

    python3 bench/profile.py --workload <cell> --seed <n> --seconds <s> \
        [--out <file.json>]

Sets the cell up as ``bench/run.py`` does, then runs two windows of the
same compiled step: one untraced, one under the profiler.  The last
line of standard output is one JSON object:

- ``untraced`` and ``traced``: each window's end-to-end metrics, and
  ``tracing_cost``: 1 - traced over untraced, per metric;
- ``metrics``: the scope readers of ``bench/metrics`` (``SCOPE_METRICS``)
  that found their scope, in ms per step;
- ``scopes``: every scope's busy and exposed ms per step;
- ``unscoped_share``: the share of device busy time under no scope;
- ``mpix_scopes``: the ``mpix.<collective>.<algorithm>.<transport>``
  scopes that ran, i.e. what ``auto`` resolved to;
- ``idle_share``, ``idle_gaps`` (longest first) and ``gap_ms_per_step``
  by name, each gap named down to the host event inside it
  (``step_dispatch/PjitFunction(train_step)``);
- ``dispatch_lag_ms``: the check of the host clock against the device's
  that the naming rests on (``scopes.dispatch_lags``): a negative lag
  means the host events sit too late;
- ``host_ms``: quartiles of the host-clock durations of the benchmark
  spans and of the dispatches (``PjitFunction(...)``), which need no
  alignment of the two clocks;
- ``unscoped_ops_ms_per_step``: the HLO instructions under no scope that
  take the most device time.

``--out`` writes the same object to a file.  The unscoped share and the
``mpix`` scopes are also logged to standard error.  With no TPU it
prints no result and exits with 3.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SCOPE_METRICS = ("train.forward_ms", "train.backward_ms",
                 "train.optimizer_ms", "train.count_psum_ms",
                 "train.grad_sync_exposed_ms", "decode.attention_ms",
                 "decode.cache_write_ms")


def profile(name: str, seed: int, seconds: float, log) -> dict:
    import jax
    from bench import harness, scopes, trace
    from bench.peaks import peaks

    ctx = harness.cell_context(ROOT, name, seed, [], log)
    ctx.devices = harness.accelerators(ctx.cell["chips"])
    harness.enable_compile_cache(ROOT)
    drv = harness.load_module(ROOT / "bench" / "drivers"
                              / f"{ctx.workload['driver']}.py").Driver(ctx)
    drv.setup()
    log(f"setup_s {time.perf_counter() - STARTED:.3f}")
    plain = drv.window(seconds, traced=False)
    tmp = tempfile.mkdtemp(prefix="bench-profile-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            win = drv.window(seconds, traced=True)
        finally:
            jax.profiler.stop_trace()
        tr = scopes.read(scopes.load(trace.xplane_path(tmp)),
                         harness.HOST_SPANS)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    ev = [h for h in tr.host if h.name == "window"]
    if not ev:
        raise RuntimeError("the trace holds no 'window' span")
    s = scopes.summarize(tr, ev[0].start_ns, ev[0].end_ns,
                         [d.id for d in ctx.devices], harness.HOST_SPANS)
    inp = harness.MetricInput(s, win, drv.counts(),
                              peaks(ctx.devices[0].device_kind),
                              ctx.cell["chips"])
    metrics = {}
    for m in SCOPE_METRICS:
        v = harness.load_module(ROOT / "bench" / "metrics"
                                / f"{m}.py").read(inp)
        if v is not None:
            metrics[m] = v
    steps = win.facts["steps"]
    per_step = lambda ns_by_dev: sum(ns_by_dev.values()) / len(
        s.devices) * 1e-6 / steps
    log(f"unscoped_share {s.unscoped_share!r}")
    log(f"mpix scopes {s.mpix_scopes()}")
    dev = ctx.devices[0]
    return {
        "workload": name, "seed": seed,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(ctx.devices)},
        "untraced": plain.metrics, "traced": win.metrics,
        "tracing_cost": {k: 1.0 - win.metrics[k] / v
                         for k, v in plain.metrics.items()},
        "steps": steps, "window_s": s.window_ns * 1e-9,
        "metrics": metrics,
        "scopes": {k: {"busy_ms": per_step(v),
                       "exposed_ms": per_step(s.scope_exposed_ns[k])}
                   for k, v in s.scope_busy_ns.items()},
        "unscoped_share": s.unscoped_share,
        "mpix_scopes": s.mpix_scopes(),
        "idle_share": s.idle_share,
        "idle_gaps": [[k, v * 1e-9] for k, v in s.gaps[:20]],
        "gap_ms_per_step": {k: v * 1e-6 / steps
                            for k, v in list(s.gap_totals().items())[:20]},
        "dispatch_lag_ms": quartiles(s.dispatch_lag_ns),
        "host_ms": {k: quartiles(v) for k, v in s.host_ns.items()},
        "unscoped_ops_ms_per_step": {
            k: v * 1e-6 / steps
            for k, v in list(s.unscoped_op_ns.items())[:25]},
    }


def quartiles(ns: list[float]) -> dict:
    """Count, least, quartiles and greatest of ``ns``, in ms."""
    import statistics
    if len(ns) < 2:
        return {"n": len(ns)}
    q = statistics.quantiles(ns, n=4)
    return {"n": len(ns), "min": min(ns) * 1e-6,
            "q": [v * 1e-6 for v in q], "max": max(ns) * 1e-6}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    log = lambda s: print(s, file=sys.stderr, flush=True)
    try:
        out = profile(args.workload, args.seed, args.seconds, log)
    except harness.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
