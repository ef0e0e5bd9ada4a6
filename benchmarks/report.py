"""Generate the EXPERIMENTS.md roofline tables from dry-run JSONs.

    PYTHONPATH=src python -m benchmarks.report \
        --baseline dryrun_baseline.json --optimized dryrun_optimized.json
"""
from __future__ import annotations

import argparse
import json

from repro import configs
from repro.configs.shapes import SHAPES

# Per-chip peaks keyed by ``jax.devices()[0].device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over four links
# (50 GB/s per link, the per-link figure the collective term uses).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm": 819e9, "ici": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks recorded for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def model_flops(arch: str, shape: str) -> float:
    cfg = configs.get_config(arch)
    n = cfg.active_param_count()
    sp = SHAPES[shape]
    if sp.kind == "train":
        toks = sp.global_batch * sp.seq_len
        return 6.0 * n * toks
    if sp.kind == "prefill":
        return 2.0 * n * sp.global_batch * sp.seq_len
    return 2.0 * n * sp.global_batch          # decode: 1 new token


def terms(r, pk):
    tc = r["flops_per_device"] / pk["flops"]
    tm = r["hbm_bytes_per_device"] / pk["hbm"]
    tl = r["collectives"]["total"] / pk["ici"]
    dom = max((tc, "compute"), (tm, "memory"), (tl, "collective"))[1]
    return tc, tm, tl, dom


def fmt(t):
    return f"{t:9.2f}" if t >= 0.01 else f"{t:9.4f}"


HINTS = {
    "compute": "more chips / lower precision",
    "memory": "fuse attention/recurrence state into VMEM (kernel path)",
    "collective": "sequence-parallel residual + staged hierarchical "
                  "collectives",
}


def table(results, pk, mesh="16x16", compare=None):
    rows = []
    comp_map = {}
    if compare:
        comp_map = {(r["arch"], r["shape"]): r for r in compare
                    if not r.get("skip") and r.get("mesh") == mesh}
    print("| arch | shape | Tcomp s | Tmem s | Tcoll s | bound | "
          "MODEL/HLO | note |")
    print("|---|---|---|---|---|---|---|---|")
    for r in results:
        if r.get("skip"):
            print(f"| {r['arch']} | {r['shape']} | — | — | — | SKIP "
                  f"(sub-quadratic only) | — | documented skip |")
            continue
        if r.get("mesh") != mesh:
            continue
        tc, tm, tl, dom = terms(r, pk)
        mf = model_flops(r["arch"], r["shape"])
        ratio = mf / (r["flops_per_device"] * r["n_devices"])
        note = HINTS[dom]
        if compare:
            b = comp_map.get((r["arch"], r["shape"]))
            if b:
                btc, btm, btl, _ = terms(b, pk)
                x = max(btc, btm, btl) / max(tc, tm, tl)
                note = f"{x:,.0f}x vs baseline bound"
        print(f"| {r['arch']} | {r['shape']} |{fmt(tc)} |{fmt(tm)} "
              f"|{fmt(tl)} | {dom} | {ratio:.2f} | {note} |")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", required=True)
    ap.add_argument("--optimized", default=None)
    ap.add_argument("--device-kind", default="TPU v5 lite",
                    help="device_kind whose peaks price the dry-run "
                         "(one of PEAKS)")
    args = ap.parse_args()
    pk = peaks(args.device_kind)
    base = json.load(open(args.baseline))["results"]
    print("### Baseline (paper-faithful defaults), single-pod 16x16, "
          "per-device terms\n")
    table(base, pk)
    if args.optimized:
        opt = json.load(open(args.optimized))["results"]
        print("\n### Optimized (hint-level 2 SP + kernel path), "
              "single-pod 16x16\n")
        table(opt, pk, compare=base)
        print("\n### Multi-pod 2x16x16 optimized (DCN axis active)\n")
        table(opt, pk, mesh="2x16x16", compare=base)


if __name__ == "__main__":
    main()
