"""Readings that the limits of ``correct`` are set from, on the chip:
the program's sound runs, the control (the reference put in the
program's place one precision step down) and the planted faults, over
many seeds in one process.  The benchmark's own runs never run this.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 3]

Prints one JSON line per seed: {"seed", "program": {number: value},
"control": {...}, "faults": {fault: {...}}}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def train_readings(drv, control: bool) -> dict:
    from bench.drivers.train import numbers
    prog = (drv.losses, drv.grad, drv.change)
    ref = drv.reference()
    out = {"program": numbers(prog, ref)}
    if control:
        b = drv.w["global_batch"]
        dp = drv.w["data_parallel"]
        out["control"] = numbers(drv.reference(fp8=True), ref)
        out["faults"] = {
            "half_batch": numbers(drv.reference(rows=slice(0, b // 2)), ref),
            # one replica's gradient, divided by the whole batch's count,
            # as the program's sync would give it with the exchange gone
            "exchange_left_out": numbers(drv.reference(
                rows=slice(0, b // max(dp, 4)), denom_rows=slice(None)),
                ref)}
    return out


def decode_readings(drv, control: bool) -> dict:
    drv.window(0.0, traced=False)        # one round of requests
    seqs = drv.sample()
    out = {"program": {"served_logit_gap": drv.gaps(seqs)}}
    if control:
        p = drv.w["prompt_len"]
        bad = seqs.copy()
        bad[:, p + 5] = (bad[:, p + 5] + 1) % drv.c["vocab_size"]
        out["control"] = {"served_logit_gap": drv.gaps(seqs, fp8_pick=True)}
        out["faults"] = {"token_altered": {
            "served_logit_gap": drv.gaps(bad)}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    m = harness.manifest(ROOT)
    chips = {c["name"]: c for c in m["workloads"]}[args.workload]["chips"]
    devices = harness.accelerators(chips)
    harness.enable_compile_cache(ROOT)
    read = {"train": train_readings, "decode": decode_readings}
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        ctx = harness.cell_context(ROOT, args.workload, seed, devices,
                                   log=lambda s: None)
        kind = ctx.workload["driver"]
        drv = harness.load_module(
            ROOT / "bench" / "drivers" / f"{kind}.py").Driver(ctx)
        drv.setup()
        out = read[kind](drv, i < args.control_seeds)
        out.update(seed=seed, seconds=time.perf_counter() - t)
        print(json.dumps(out), flush=True)
        del drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
