"""The benchmark's harness: finds a cell's pieces by name and runs it.

Everything that belongs to one cell is data or a file of its own, found
from ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the configuration as it is run;
- ``bench/workloads/<traffic>.json``: the traffic mix, with the name of
  the driver that generates it;
- ``bench/drivers/<driver>.py``: one general generator per kind of work;
- ``bench/limits/<cell>.json``: the limits of the numbers that decide
  ``correct``, each with the readings it was set from;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A new cell, configuration, traffic mix or metric is new files and new
``BENCHMARK.json`` entries; no existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HOST_SPANS = ("window", "step_dispatch", "loss_readback",
              "decode_dispatch", "token_readback", "cache_restore")
# programs compiled, or loaded from the persistent cache
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number that decides ``correct``: at most ``limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What a driver's measured window did.  ``metrics``: end-to-end
    values by name; ``facts``: counts the per-layer readers use."""
    seconds: float
    attempted: int
    failed: int
    metrics: dict
    facts: dict


@dataclasses.dataclass
class Context:
    root: Path
    cell: dict
    config: dict
    workload: dict
    limits: dict
    seed: int
    devices: list
    log: object = print


@dataclasses.dataclass
class MetricInput:
    """What a per-layer reader gets: the traced window's summary (None
    where the run was not traced on a device), the driver's window and
    counts, the device's peaks and the number of chips."""
    summary: object
    window: Window
    counts: dict
    peaks: dict
    chips: int

    @property
    def device_s(self) -> float | None:
        """Seconds in which the device ran an operation in the traced
        window (mean over the chips), or None where there is no trace."""
        return None if self.summary is None else (
            self.summary.mean_busy_ns * 1e-9)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path, under a module name made from it."""
    name = "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix(
        "").parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell_context(root: Path, name: str, seed: int, devices,
                 log=print) -> Context:
    m = manifest(root)
    cells = {c["name"]: c for c in m["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in m["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    workload = load_json(root / "bench" / "workloads"
                         / f"{cell['traffic']}.json")
    limits = load_json(root / "bench" / "limits" / f"{name}.json")
    return Context(root, cell, config, workload, limits, seed, devices, log)


def _reported(entries, cell: str) -> list:
    return [e for e in entries if cell in e.get("workloads", [cell])]


def end_to_end(m: dict, cell: str) -> list:
    """The end-to-end metrics ``cell`` reports."""
    return _reported(m["end_to_end"], cell)


def per_layer(m: dict, cell: str) -> list:
    """The per-layer metrics ``cell`` reports: those that list it, and
    those without a list whose ``moves`` the cell reports."""
    mine = {e["name"] for e in end_to_end(m, cell)}
    return [e for e in m["per_layer"]
            if cell in e.get("workloads", [cell] if e["moves"] in mine
                             else [])]


def accelerators(chips: int):
    """The first ``chips`` TPU devices; raises ``NoAccelerator``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU: its first device is on "
                            f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at the fixed
    ``<checkout>/.jax_cache``, or where ``JAX_COMPILATION_CACHE_DIR``
    says; every program is cached, however quickly it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class _Compiles:
    """Counts the programs compiled or loaded while ``on``."""

    def __init__(self):
        import jax
        self.n, self.on = 0, False
        jax.monitoring.register_event_listener(self)
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, *args, **kwargs):
        if self.on and event in COMPILE_EVENTS:
            self.n += 1


_compiles: _Compiles | None = None


def _window(drv, seconds, traced):
    """The driver's window, and the number of programs it compiled or
    loaded: set-up should have loaded every one."""
    global _compiles
    _compiles = _compiles or _Compiles()
    _compiles.n, _compiles.on = 0, True
    try:
        return drv.window(seconds, traced=traced), _compiles.n
    finally:
        _compiles.on = False


def _traced(drv, ctx, seconds):
    """Run the window under the profiler; returns (window, compiles,
    summary of the trace over the window)."""
    import jax
    from bench import trace
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            win, compiles = _window(drv, seconds, True)
        finally:
            jax.profiler.stop_trace()
        tr = trace.read_dir(tmp, HOST_SPANS)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    ev = [h for h in tr.host if h.name == "window"]
    if not ev:
        raise RuntimeError("the trace holds no 'window' span")
    return win, compiles, trace.summarize(
        tr, ev[0].start_ns, ev[0].end_ns, [d.id for d in ctx.devices])


def run(name: str, seed: int, seconds: float, traced: bool, *,
        root: Path = ROOT, started: float | None = None,
        on_accelerator: bool = True, log=None) -> dict:
    """Run one cell and return the result line as a dict.

    ``on_accelerator=False`` is the tests' path: it takes whatever
    devices JAX has.  ``bench/run.py`` always asks for the chip."""
    started = time.perf_counter() if started is None else started
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    m = manifest(root)
    ctx = cell_context(root, name, seed, [], log)
    chips = ctx.cell["chips"]
    import jax
    from bench.peaks import peaks
    devices = (accelerators(chips) if on_accelerator
               else jax.devices()[:chips])
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} devices, JAX sees "
                            f"{len(devices)}")
    ctx.devices = devices
    pk = peaks(devices[0].device_kind) if on_accelerator else {}
    enable_compile_cache(root)
    drv = load_module(root / "bench" / "drivers"
                      / f"{ctx.workload['driver']}.py").Driver(ctx)
    drv.setup()
    setup_s = time.perf_counter() - started
    log(f"setup_s {setup_s:.3f}")
    log(f"setup memory_peak_bytes {_memory_peak(devices)}")

    summary = None
    if traced and on_accelerator:
        win, compiles, summary = _traced(drv, ctx, seconds)
    else:
        win, compiles = _window(drv, seconds, traced)
    mem = _memory_peak(devices)
    log(f"window_compiles {compiles}")
    log(f"memory_peak_bytes {mem}")
    counts = drv.counts()
    t = time.perf_counter()
    checks = drv.check()
    log(f"check_s {time.perf_counter() - t:.3f}")

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    if traced:
        metrics = {}
        inp = MetricInput(summary, win, counts, pk, chips)
        for e in per_layer(m, name):
            v = load_module(root / "bench" / "metrics"
                            / f"{e['name']}.py").read(inp)
            if v is not None:
                metrics[e["name"]] = {"value": v, "unit": e["unit"]}
        if summary is not None:
            device["busy_s"] = summary.mean_busy_ns * 1e-9
            device["window_s"] = summary.window_ns * 1e-9
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for e in end_to_end(m, name):
            if e["name"] != "setup_s":
                metrics[e["name"]] = {"value": win.metrics[e["name"]],
                                      "unit": e["unit"]}
    out = {"correct": bool(all(c.ok for c in checks)) and win.failed == 0,
           "attempted": win.attempted, "failed": win.failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = summary.breakdown()
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out
