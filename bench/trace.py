"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read into plain ``Event`` tuples first (``read``), so that
the arithmetic (``busy``, ``op_totals``, ``gaps``) runs on lists the
tests can write by hand.  Device events come from the planes named
``/device:<KIND>:<n>``: their ``XLA Ops`` line holds one event per HLO
operation.  Host spans are the benchmark's own ``TraceAnnotation`` names, recorded on the
host plane's lines.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, Sequence

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
HOST_PLANE = "/host:CPU"
# a TPU op event is named by its HLO text: "%fusion.12 = bf16[8,64]{...} ..."
# HLO collectives, async halves included (all-reduce-start, -done, ...)
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|ragged-all-to-all)")
_HLO = re.compile(r"^%?(?P<name>[^\s=]+)(\s*=\s*(?P<shape>\w+\[[^\]]*\]))?")
ENCLOSING = ("window",)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """Per device id: its op events; ``host``: the host spans, by
    name."""
    ops: dict[int, list[Event]]
    host: list[Event]


def xplane_path(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def read(profile, host_names: Iterable[str] = ()) -> Trace:
    """Events of a ``jax.profiler.ProfileData`` (or anything with its
    ``planes`` / ``lines`` / ``events`` shape).  ``host_names``: the host
    span names to keep."""
    keep = set(host_names)
    ops: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(2))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name == HOST_PLANE and keep:
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.name in keep)
    return Trace(ops, host)


def read_dir(trace_dir: str, host_names: Iterable[str] = ()) -> Trace:
    from jax.profiler import ProfileData
    return read(ProfileData.from_file(xplane_path(trace_dir)), host_names)


def clip(events: Sequence[Event], lo: float, hi: float) -> list[Event]:
    """The parts of ``events`` that lie inside [lo, hi]."""
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def union(events: Sequence[Event]) -> list[tuple[float, float]]:
    """The merged [start, end) intervals that ``events`` cover."""
    spans = sorted((e.start_ns, e.end_ns) for e in events if e.dur_ns > 0)
    out: list[list[float]] = []
    for s, t in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_ns(events: Sequence[Event]) -> float:
    """Length of the union of the events' intervals: the time in which
    at least one of them ran."""
    return sum(t - s for s, t in union(events))


def op_label(text: str) -> str:
    """``%fusion.12 = bf16[8,64]{1,0} fusion(...)`` -> ``fusion.12
    bf16[8,64]``: the HLO instruction and its result shape."""
    m = _HLO.match(text)
    if not m:
        return text[:80]
    return m.group("name") + (f" {m.group('shape')}" if m.group("shape")
                              else "")


def op_kind(text: str) -> str:
    """The instruction's name without its instance suffixes:
    ``%all-reduce-start.3.clone = ...`` -> ``all-reduce-start``."""
    m = _HLO.match(text)
    return re.sub(r"(\.\d+|\.clone)+$", "", m.group("name") if m else text)


def op_totals(events: Sequence[Event]) -> dict[str, float]:
    """Summed duration per HLO instruction (``op_label``)."""
    out: dict[str, float] = {}
    for e in events:
        key = op_label(e.name)
        out[key] = out.get(key, 0.0) + e.dur_ns
    return out


def collective_ns(events: Sequence[Event]) -> float:
    """Summed device duration of the collective HLO ops."""
    return sum(e.dur_ns for e in events if COLLECTIVE.match(op_kind(e.name)))


def gaps(events: Sequence[Event], lo: float, hi: float,
         host: Sequence[Event]) -> list[tuple[str, float]]:
    """The idle gaps of the device inside [lo, hi], longest first, each
    named by the host span that covers most of it (``"none"`` where no
    span does), as (name, ns)."""
    busy = union(clip(events, lo, hi))
    edges = [lo] + [x for s, t in busy for x in (s, t)] + [hi]
    out = []
    for s, t in zip(edges[0::2], edges[1::2]):
        if t <= s:
            continue
        best, cover = "none", 0.0
        for h in host:
            c = min(h.end_ns, t) - max(h.start_ns, s)
            if c > cover:
                best, cover = h.name, c
        out.append((best, t - s))
    out.sort(key=lambda g: -g[1])
    return out


@dataclasses.dataclass
class Summary:
    """What the metric readers and the result line take from a trace."""
    window_ns: float
    devices: list[int]
    busy_ns: dict[int, float]
    collective_ns: dict[int, float]
    op_ns: dict[str, float]          # per HLO instruction, mean over devices
    gaps: list[tuple[str, float]]    # device 0's idle gaps, longest first

    @property
    def mean_busy_ns(self) -> float:
        return sum(self.busy_ns.values()) / len(self.busy_ns)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_ns / self.window_ns

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in self.gaps[:top]]}


def summarize(tr: Trace, lo: float, hi: float,
              devices: Sequence[int]) -> Summary:
    """Reduce ``tr`` over the window [lo, hi] (ns, the trace's clock) on
    ``devices``.  Raises where a device shows no operation at all."""
    busy, coll, ops = {}, {}, {}
    for d in devices:
        evs = clip(tr.ops.get(d, []), lo, hi)
        if not evs:
            raise ValueError(f"the trace shows no operation on device {d} "
                             f"inside the window")
        busy[d] = busy_ns(evs)
        coll[d] = collective_ns(evs)
        for k, v in op_totals(evs).items():
            ops[k] = ops.get(k, 0.0) + v / len(devices)
    inner = [h for h in tr.host if h.name not in ENCLOSING]
    return Summary(hi - lo, list(devices), busy, coll, ops,
                   gaps(tr.ops.get(devices[0], []), lo, hi, inner))
