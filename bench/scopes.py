"""Device time by the program's named scopes, and idle gaps named down
to the host event inside them.

The program puts ``jax.named_scope`` names on its layer boundaries
(``train.loss``, ``train.grad_sync``, ``mpix.<collective>.<algorithm>.
<transport>``, ``decode.attention`` ...).  On a TPU, an op event of a
device plane's ``XLA Ops`` line carries its scope path in the ``tf_op``
stat of its event metadata, e.g.
``jit(train_step)/transpose(jvp(train.loss))/while/body/dot_general:``.
``jax.profiler.ProfileData`` hands out an event's own stats only, so
this module reads the ``.xplane.pb`` with the XSpace protobuf module
that TensorFlow's TSL installs (``xplane_pb2``), loaded by its path, so
that TensorFlow itself is never imported.

A scope is one component of the path.  ``jvp(train.loss)`` (forward)
and ``transpose(jvp(train.loss))`` (backward) are different components,
so one scope in the program splits the two.  The reduction runs on
plain ``Op`` and ``HostEvent`` lists that the tests write by hand.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import importlib.util
from pathlib import Path
from typing import Iterable, Sequence

import numpy as np

from bench import trace

# the scopes the per-layer readers read, besides every ``mpix.*`` one
SCOPES = ("jvp(train.loss)", "transpose(jvp(train.loss))",
          "train.count_psum", "train.grad_sync", "train.optimizer",
          "decode.attention", "decode.cache_write")
# ops whose event spans the ops of their body: no work of their own
CONTAINERS = ("while", "conditional", "call")
SCOPE_STAT = "tf_op"


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    dur_ns: float
    path: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@functools.lru_cache(maxsize=None)
def components(path: str) -> frozenset[str]:
    """The scopes of an op's path that the readers know: ``SCOPES``
    and every ``mpix.*``."""
    return frozenset(c for c in path.split("/")
                     if c in SCOPES or c.startswith("mpix."))


@functools.lru_cache(maxsize=None)
def _container(name: str) -> bool:
    return trace.op_kind(name) in CONTAINERS


@dataclasses.dataclass(frozen=True)
class HostEvent:
    name: str
    start_ns: float
    dur_ns: float
    thread: str

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class ScopedTrace:
    """Per device id: its op events with their scope paths; ``host``:
    every event of the host threads that hold a benchmark span."""
    ops: dict[int, list[Op]]
    host: list[HostEvent]


@functools.cache
def xplane_pb2():
    """TSL's generated XSpace module, loaded from its file."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ImportError("reading scope paths needs the XSpace protobuf "
                          "module that TensorFlow installs")
    path = (Path(spec.submodule_search_locations[0]) / "tsl" / "profiler"
            / "protobuf" / "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load(path: str):
    """The XSpace message of an ``.xplane.pb`` file."""
    space = xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def parse_text(text: str):
    """The XSpace message of a text proto (the tests' traces)."""
    from google.protobuf import text_format
    return text_format.Parse(text, xplane_pb2().XSpace())


def scope_path(tf_op: str) -> str:
    """``jit(f)/train.loss/add:`` -> ``jit(f)/train.loss/add``: the path
    without the op type XProf appends after the last colon."""
    return tf_op.rpartition(":")[0] if ":" in tf_op else tf_op


def _events(line):
    for e in line.events:
        yield e, line.timestamp_ns + e.offset_ps * 1e-3, e.duration_ps * 1e-3


def read(space, span_names: Iterable[str]) -> ScopedTrace:
    """Op events of each ``/device:<KIND>:<n>`` plane's ``XLA Ops`` line
    with their scope paths, and the events of the host threads on
    which any of ``span_names`` was recorded."""
    spans = set(span_names)
    ops: dict[int, list[Op]] = {}
    host: list[HostEvent] = []
    for plane in space.planes:
        names = {k: v.name for k, v in plane.event_metadata.items()}
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            stat = [k for k, v in plane.stat_metadata.items()
                    if v.name == SCOPE_STAT]
            paths = {}
            for k, md in plane.event_metadata.items():
                for s in md.stats:
                    if s.metadata_id in stat:
                        paths[k] = scope_path(
                            s.str_value or plane.stat_metadata[
                                s.ref_value].name)
            dev = ops.setdefault(int(m.group(2)), [])
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    dev.extend(Op(names[e.metadata_id], s, d,
                                  paths.get(e.metadata_id, ""))
                               for e, s, d in _events(line))
        elif plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                if any(names[e.metadata_id] in spans for e in line.events):
                    host.extend(HostEvent(names[e.metadata_id], s, d,
                                          line.name)
                                for e, s, d in _events(line))
    return ScopedTrace(ops, host)


def _clip(ops: Sequence[Op], lo: float, hi: float) -> list[Op]:
    out = []
    for o in ops:
        s, t = max(o.start_ns, lo), min(o.end_ns, hi)
        if t > s:
            out.append(Op(o.name, s, t - s, o.path))
    return out


def _covered(start: np.ndarray, end: np.ndarray) -> float:
    """Length of the union of the intervals [start, end), ``start``
    sorted: ``trace.busy_ns`` on arrays."""
    if not len(start):
        return 0.0
    reach = np.maximum.accumulate(end)
    first = np.flatnonzero(np.r_[True, start[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, len(start) - 1]
    return float((reach[last] - start[first]).sum())


def scope_times(ops: Sequence[Op]) -> tuple[dict, dict]:
    """Per scope found in ``ops`` (``components``): the busy union of
    its ops, and the part of that union during which no op outside it
    runs (exposed: the union of all ops less the union of those
    outside), in ns.  Control-flow ops (``CONTAINERS``) are left out of
    both: their events span their body's ops and would hide every
    overlap."""
    work = sorted((o for o in ops if not _container(o.name)),
                  key=lambda o: o.start_ns)
    start = np.array([o.start_ns for o in work], float)
    end = np.array([o.end_ns for o in work], float)
    sets: dict[frozenset, int] = {}
    ids = np.array([sets.setdefault(components(o.path), len(sets))
                    for o in work], int)
    total = _covered(start, end)
    busy, exposed = {}, {}
    for sc in sorted(set().union(*sets)):
        inside = np.isin(ids, [i for c, i in sets.items() if sc in c])
        busy[sc] = _covered(start[inside], end[inside])
        exposed[sc] = total - _covered(start[~inside], end[~inside])
    return busy, exposed


def _inner(span: HostEvent, host: Sequence[HostEvent],
           starts: list[float], s: float, t: float) -> str | None:
    """The innermost host event on ``span``'s thread, inside ``span``,
    that covers more than half of the gap [s, t]."""
    best = None
    lo = bisect.bisect_left(starts, span.start_ns)
    hi = bisect.bisect_right(starts, span.end_ns)
    for h in host[lo:hi]:
        if (h is span or h.thread != span.thread
                or h.end_ns > span.end_ns):
            continue
        cover = min(h.end_ns, t) - max(h.start_ns, s)
        if 2 * cover > t - s and (best is None or h.dur_ns < best.dur_ns):
            best = h
    return None if best is None else best.name


def idle(ops: Sequence[Op], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The intervals inside [lo, hi] in which no op runs."""
    busy = trace.union(_clip(ops, lo, hi))
    edges = [lo] + [x for s, t in busy for x in (s, t)] + [hi]
    return [(s, t) for s, t in zip(edges[0::2], edges[1::2]) if t > s]


def dispatch_lags(ops: Sequence[Op], lo: float, hi: float,
                  host: Sequence[HostEvent], min_ns: float = 1e5
                  ) -> list[float]:
    """A check of the host clock against the device's, which naming a
    gap by host events rests on.  For each idle gap inside (lo, hi) of
    at least ``min_ns``: the time from the start of the first host
    dispatch (``PjitFunction(...)``) that starts after the gap does to
    the gap's end, in ns.  In a loop that waits for each step, that
    dispatch is what ends the gap, and the device cannot start before
    it: a negative lag shows the host's events placed at least that
    much too late against the device's."""
    pjit = sorted(h.start_ns for h in host
                  if h.name.startswith("PjitFunction("))
    out = []
    for s, t in idle(ops, lo, hi):
        i = bisect.bisect_left(pjit, s)
        if t - s >= min_ns and lo < s and t < hi and i < len(pjit):
            out.append(t - pjit[i])
    return out


def gaps(ops: Sequence[Op], lo: float, hi: float,
         host: Sequence[HostEvent], spans: Iterable[str]
         ) -> list[tuple[str, float]]:
    """The idle gaps of the device inside [lo, hi], longest first, as
    (name, ns).  Each is named as ``trace.gaps`` names it, by the
    benchmark span that covers most of it (``"none"`` where none does),
    followed by ``/`` and the innermost host event on that span's thread
    that covers more than half of the gap, where there is one:
    ``step_dispatch/PjitFunction(train_step)``."""
    keep = set(spans) - set(trace.ENCLOSING)
    host = sorted(host, key=lambda h: h.start_ns)
    starts = [h.start_ns for h in host]
    bench = [h for h in host if h.name in keep]
    bstarts = [h.start_ns for h in bench]
    longest = max((h.dur_ns for h in bench), default=0.0)
    out = []
    for s, t in idle(ops, lo, hi):
        span, cover = None, 0.0
        # the spans that start before the gap ends and may reach into it
        for h in bench[bisect.bisect_left(bstarts, s - longest):
                       bisect.bisect_left(bstarts, t)]:
            c = min(h.end_ns, t) - max(h.start_ns, s)
            if c > cover:
                span, cover = h, c
        name = "none"
        if span is not None:
            inner = _inner(span, host, starts, s, t)
            name = span.name + (f"/{inner}" if inner else "")
        out.append((name, t - s))
    out.sort(key=lambda g: -g[1])
    return out


@dataclasses.dataclass
class Summary:
    """What the scope readers take from a traced window: per scope and
    device, busy and exposed ns; the share of busy time under no scope
    (mean over the chips); device 0's idle gaps, named, and its
    ``dispatch_lags``."""
    window_ns: float
    devices: list[int]
    busy_ns: dict[int, float]
    scope_busy_ns: dict[str, dict[int, float]]
    scope_exposed_ns: dict[str, dict[int, float]]
    unscoped_share: float
    gaps: list[tuple[str, float]]
    dispatch_lag_ns: list[float] = dataclasses.field(default_factory=list)
    # per HLO instruction (``trace.op_label``) under no scope, control
    # flow left out: summed ns, mean over the chips
    unscoped_op_ns: dict[str, float] = dataclasses.field(
        default_factory=dict)
    # host-clock durations of the benchmark spans and the dispatches
    # (``PjitFunction(...)``) inside the window, by name: what the host
    # spent, with no reference to the device's clock
    host_ns: dict[str, list[float]] = dataclasses.field(
        default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - sum(self.busy_ns.values()) / len(
            self.busy_ns) / self.window_ns

    def mpix_scopes(self) -> list[str]:
        return [s for s in self.scope_busy_ns if s.startswith("mpix.")]

    def gap_totals(self) -> dict[str, float]:
        """Summed idle ns per gap name."""
        out: dict[str, float] = {}
        for k, v in self.gaps:
            out[k] = out.get(k, 0.0) + v
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def summarize(tr: ScopedTrace, lo: float, hi: float,
              devices: Sequence[int], spans: Iterable[str]) -> Summary:
    """Reduce ``tr`` over the window [lo, hi] on ``devices``.  Raises
    where a device shows no operation inside the window."""
    busy, sb, se, unscoped, loose = {}, {}, {}, [], {}
    for d in devices:
        ops = _clip(tr.ops.get(d, []), lo, hi)
        if not ops:
            raise ValueError(f"the trace shows no operation on device {d} "
                             f"inside the window")
        busy[d] = trace.busy_ns(ops)
        unscoped.append(1.0 - trace.busy_ns(
            [o for o in ops if components(o.path)]) / busy[d])
        b, e = scope_times(ops)
        for sc in b:
            sb.setdefault(sc, {})[d] = b[sc]
            se.setdefault(sc, {})[d] = e[sc]
        for o in ops:
            if not components(o.path) and not _container(o.name):
                k = trace.op_label(o.name)
                loose[k] = loose.get(k, 0.0) + o.dur_ns / len(devices)
    keep = set(spans) - set(trace.ENCLOSING)
    host: dict[str, list[float]] = {}
    for h in tr.host:
        if (lo <= h.start_ns and h.end_ns <= hi
                and (h.name in keep or h.name.startswith("PjitFunction("))):
            host.setdefault(h.name, []).append(h.dur_ns)
    first = tr.ops.get(devices[0], [])
    return Summary(hi - lo, list(devices), busy, sb, se,
                   sum(unscoped) / len(unscoped),
                   gaps(first, lo, hi, tr.host, spans),
                   dispatch_lags(first, lo, hi, tr.host),
                   dict(sorted(loose.items(), key=lambda kv: -kv[1])), host)


def per_step_ms(m, table: str, scope: str) -> float | None:
    """A reader's value: ``scope``'s ns in ``m.summary``'s ``table``
    (``scope_busy_ns`` or ``scope_exposed_ns``), mean over the chips,
    per step of the window, in ms.  None where the run was not traced,
    the summary has no scope table, or no op of the scope ran."""
    by_dev = (getattr(m.summary, table, None) or {}).get(scope)
    steps = m.window.facts.get("steps")
    if not by_dev or not steps:
        return None
    devs = m.summary.devices
    return sum(by_dev.get(d, 0.0) for d in devs) / len(devs) * 1e-6 / steps
