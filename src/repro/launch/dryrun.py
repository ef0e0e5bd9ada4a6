import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell
with ShapeDtypeStruct inputs (no allocation), print memory_analysis()
and cost_analysis(), and extract the collective schedule for §Roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --json out.json
"""
import argparse
import json
import re
import sys
import time

import jax

from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES, runnable
from repro.launch.mesh import make_production_mesh
from repro.launch import specs as SPECS
from repro.launch.hlo_analysis import analyse_hlo

# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_DT_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
             "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
             "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(s: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DT_BYTES[dt]
    return total


def collective_bytes(hlo: str) -> dict:
    """Per-device wire bytes by collective kind, from the compiled HLO.

    Uses result shapes + group size G with standard wire-cost factors:
      all-gather         (G-1)/G * result      (received)
      all-reduce         2*(G-1)/G * result    (ring rs+ag)
      reduce-scatter     (G-1)/G * result * G  (= (G-1) * result sent)
      all-to-all         (G-1)/G * result
      collective-permute 1.0    * result
    """
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0, "count": 0}
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if m is None or "-done" in line:
            continue
        shape_s, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(shape_s)
        if nbytes == 0:
            continue
        g = None
        gm = _GROUPS_RE.search(line)
        if gm:
            g = int(gm.group(2))
        else:
            gl = _GROUPS_LIST_RE.search(line)
            if gl:
                g = len([x for x in gl.group(1).split(",") if x.strip()])
        g = g or 2
        if kind == "all-gather":
            wire = nbytes * (g - 1) / g
        elif kind == "all-reduce":
            wire = 2 * nbytes * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = nbytes * (g - 1)
        elif kind == "all-to-all":
            wire = nbytes * (g - 1) / g
        else:
            wire = float(nbytes)
        out[kind] += wire
        out["count"] += 1
    out["total"] = sum(v for k, v in out.items()
                       if k not in ("count", "total"))
    return out


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               train_overrides: dict | None = None, hint_level: int = 1):
    """Lower + compile one cell; returns (lowered, compiled, meta)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train import sharding
    from repro.train.step import TrainOptions, make_train_step
    from repro.serve.step import (ServeOptions, make_prefill_step,
                                  jit_decode_step)

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.models.common import set_shard_mesh
    set_shard_mesh(mesh, level=hint_level)
    kind, ins = SPECS.input_specs(arch, shape_name)
    d_axes = sharding.data_axes(mesh)
    to_sh = lambda spec_tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))

    with jax.set_mesh(mesh):
        if kind == "train":
            opts = TrainOptions(**(train_overrides or {}))
            from repro.train.step import state_specs
            state = SPECS.state_shapes(cfg, opts)
            sspec = state_specs(state, cfg, mesh, opts)
            bspec = jax.tree.map(lambda _: P(d_axes), ins)
            step = make_train_step(cfg, mesh, opts)
            jitted = jax.jit(step, in_shardings=(to_sh(sspec),
                                                 to_sh(bspec)),
                             out_shardings=(to_sh(sspec), None))
            lowered = jitted.lower(state, ins)
        elif kind == "prefill":
            from repro.models.model import init_params
            sopts = ServeOptions(use_kernel=(train_overrides or {}).get(
                "use_kernel", False))
            params = jax.eval_shape(
                lambda: init_params(jax.random.key(0), cfg))
            pspec = sharding.param_specs(params, cfg, mesh)
            bspec = jax.tree.map(lambda _: P(d_axes), ins)
            pre = make_prefill_step(cfg, mesh, sopts)
            vshard = ("model" if cfg.vocab_size % mesh.shape["model"] == 0
                      else None)          # whisper's 51865 is odd
            jitted = jax.jit(pre, in_shardings=(to_sh(pspec),
                                                to_sh(bspec)),
                             out_shardings=NamedSharding(
                                 mesh, P(d_axes, None, vshard)))
            lowered = jitted.lower(params, ins)
        else:  # decode
            long = shape_name.startswith("long")
            sopts = ServeOptions(long_context=long)
            from repro.models.model import init_params
            params = jax.eval_shape(
                lambda: init_params(jax.random.key(0), cfg))
            jitted, _ = jit_decode_step(cfg, mesh, sopts, params,
                                        ins["cache"])
            args = [params, ins["cache"], ins["tokens"]]
            if "cross_src" in ins:
                args.append(ins["cross_src"])
            lowered = jitted.lower(*args)

        compiled = lowered.compile()
    return lowered, compiled, {"kind": kind, "mesh": mesh}


def analyse(arch: str, shape_name: str, *, multi_pod: bool,
            train_overrides=None, verbose=True, hint_level: int = 1):
    t0 = time.time()
    lowered, compiled, meta = lower_cell(
        arch, shape_name, multi_pod=multi_pod,
        train_overrides=train_overrides, hint_level=hint_level)
    t1 = time.time()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    walk = analyse_hlo(hlo)      # trip-count-corrected per-device costs
    n_dev = 512 if multi_pod else 256
    res = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": meta["kind"],
        "compile_s": round(t1 - t0, 1),
        "flops_per_device": walk["flops"],
        "hbm_bytes_per_device": walk["hbm_bytes"],
        "collectives": {**walk["coll"], "count": walk["coll_count"],
                        "total": walk["coll_total"]},
        "mem": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes
                           + mem.temp_size_in_bytes),
        },
        "n_devices": n_dev,
    }
    if verbose:
        coll = res["collectives"]
        print(f"[{arch} x {shape_name} x {res['mesh']}] "
              f"kind={meta['kind']} compile={res['compile_s']}s")
        print(f"  flops/dev={walk['flops']:.3e}  "
              f"hbm bytes/dev={walk['hbm_bytes']:.3e}")
        print(f"  args={mem.argument_size_in_bytes/2**30:.2f}GiB  "
              f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB  "
              f"out={mem.output_size_in_bytes/2**30:.2f}GiB")
        print(f"  collective wire bytes/dev={coll['total']:.3e} "
              f"({coll['count']:.0f} ops: "
              + ", ".join(f"{k}={v:.2e}" for k, v in coll.items()
                          if k not in ('count', 'total') and v) + ")")
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cells", default=None,
                    help="comma list of arch:shape pairs")
    ap.add_argument("--json", default=None)
    ap.add_argument("--dp-mode", default="fsdp")
    ap.add_argument("--moe-mode", default="mpix_ep")
    ap.add_argument("--ep-alltoall", default="xla")
    ap.add_argument("--remat", default="true")
    ap.add_argument("--hint-level", type=int, default=1)
    ap.add_argument("--use-kernel", action="store_true",
                    help="kernel path; on CPU lowers HBM-equivalent "
                         "surrogates (REPRO_KERNEL_SURROGATE)")
    ap.add_argument("--ep-capacity", type=float, default=1.25)
    args = ap.parse_args(argv)

    if args.use_kernel:
        os.environ["REPRO_KERNEL_SURROGATE"] = "1"
    overrides = {"dp_mode": args.dp_mode, "moe_mode": args.moe_mode,
                 "ep_alltoall": args.ep_alltoall,
                 "remat": args.remat.lower() == "true",
                 "use_kernel": args.use_kernel,
                 "ep_capacity": args.ep_capacity}

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    elif args.cells:
        cells = [tuple(c.split(":")) for c in args.cells.split(",")]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results, failures = [], []
    for a, s in cells:
        if not runnable(a, s):
            print(f"[{a} x {s}] SKIP (documented: sub-quadratic only)")
            results.append({"arch": a, "shape": s, "skip": True})
            continue
        for mp in meshes:
            try:
                results.append(analyse(a, s, multi_pod=mp,
                                       train_overrides=overrides,
                                       hint_level=args.hint_level))
            except Exception as e:  # noqa: BLE001 — report and continue
                print(f"[{a} x {s} x {'multi' if mp else 'single'}] "
                      f"FAILED: {type(e).__name__}: {e}")
                failures.append((a, s, mp, str(e)[:500]))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"results": results,
                       "failures": failures}, f, indent=1)
    print(f"\n{len(results)} cells analysed, {len(failures)} failures")
    if failures:
        for f_ in failures:
            print("  FAIL:", f_[0], f_[1], "multi" if f_[2] else "single")
        sys.exit(1)


if __name__ == "__main__":
    main()
