"""Self-verifying, self-healing execution of compiled schedules.

The defense half of the chaos story (``core.chaos`` is the attack
half): ``ResilientExec`` wraps the armed/pipelined ``CompiledExec`` run
path with the recovery ladder

    verify -> retry/backoff -> transport fallback -> algorithm refit
           -> typed ``UnrecoverableError``

so a misbehaving substrate degrades a collective to a slower-but-
correct path instead of wedging the loop or silently returning wrong
data.  The acceptance oracle is metamorphic: under any seeded fault
campaign the recovered output is **bitwise identical** to the
fault-free run, or a typed error is raised — never a silent mismatch.

Integrity checking (the ``verify=`` knob):

  * ``"off"``    — no checks; faults must be *detected* (raised
    ``TransportError``, deadline overrun) to trigger recovery.
  * ``"canary"`` — one O(result) pass, NO second execution: a canary
    slot row (``schedule.add_canary_slot``) seeded with a deterministic
    pattern rides through the transport's staging buffer and is
    compared bitwise after the run; the input buffer's checksum is
    re-verified; and (finite inputs) the result region is scanned for
    non-finite values.  Catches NaN sprays and canary-hitting
    corruption.
  * ``"full"``   — additionally compares the result region bitwise
    against ONE ``SimTransport.run_reference`` execution of the
    original schedule (computed once per call, shared across retries —
    the Hunold continuous-verification mode).  Catches everything,
    costs one reference execution; ``tuner.verify_overhead_s`` prices
    both modes.

Transport fallback walks ``ladder`` (default pallas -> shardmap -> sim
-> sim-reference); a rung the host cannot serve (shardmap without
enough devices) is skipped with a recorded reason.  Algorithm refit
reuses the selector's ``NotApplicable`` ladder (the PR 8 elastic-swap
machinery): when every rung fails for the current schedule, the next
algorithm for the same collective is built and the ladder re-runs.
Every decision lands in a ``DegradationReport``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import sys
import time

import numpy as np

import jax

from repro.core.schedule import (CommSchedule, NotApplicable,
                                 add_canary_slot)
from repro.core.topology import Topology
from repro.core.transport import (PallasTransport, ShardMapTransport,
                                  SimTransport, TransportError)

VERIFY_MODES = ("off", "canary", "full")
RUNGS = ("pallas", "shardmap", "sim", "reference")


@dataclasses.dataclass(frozen=True)
class ResilienceOptions:
    """Knobs of the recovery ladder (``resilience=`` everywhere).

    verify:       "off" | "canary" | "full" (see module docstring).
    max_retries:  extra attempts per rung after the first.
    backoff_s:    first retry delay; each retry multiplies by
                  ``backoff_mult`` (exponential backoff).
    deadline_s:   per-attempt wall-clock bound; an attempt past it is
                  a timeout fault even if the result arrived (None =
                  no deadline).
    ladder:       transport rungs, tried in order.
    refit:        when every rung fails, walk the selector's algorithm
                  ladder (requires the collective name to be known).
    """

    verify: str = "canary"
    max_retries: int = 2
    backoff_s: float = 1e-3
    backoff_mult: float = 2.0
    deadline_s: float | None = None
    ladder: tuple = RUNGS
    refit: bool = True

    def __post_init__(self):
        if self.verify not in VERIFY_MODES:
            raise ValueError(f"verify must be one of {VERIFY_MODES}, "
                             f"got {self.verify!r}")
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, "
                             f"got {self.max_retries}")
        if not (np.isfinite(self.backoff_s) and self.backoff_s >= 0):
            raise ValueError(f"backoff_s must be finite >= 0, "
                             f"got {self.backoff_s}")
        if not (np.isfinite(self.backoff_mult) and self.backoff_mult >= 1):
            raise ValueError(f"backoff_mult must be finite >= 1, "
                             f"got {self.backoff_mult}")
        if self.deadline_s is not None and not (
                np.isfinite(self.deadline_s) and self.deadline_s > 0):
            raise ValueError(f"deadline_s must be finite > 0 or None, "
                             f"got {self.deadline_s}")
        object.__setattr__(self, "ladder", tuple(self.ladder))
        if not self.ladder:
            raise ValueError("ladder must name at least one rung")
        for rung in self.ladder:
            if rung not in RUNGS:
                raise ValueError(f"unknown ladder rung {rung!r}; "
                                 f"expected rungs from {RUNGS}")


def resolve_resilience(resilience) -> ResilienceOptions | None:
    """Normalize the public ``resilience=`` argument: None/False = off
    entirely (zero overhead), True = defaults, a verify-mode string, a
    dict of option overrides, or a ``ResilienceOptions``."""
    if resilience is None or resilience is False:
        return None
    if resilience is True:
        return ResilienceOptions()
    if isinstance(resilience, ResilienceOptions):
        return resilience
    if isinstance(resilience, str):
        if resilience not in VERIFY_MODES:
            raise ValueError(
                f"unknown resilience preset {resilience!r}; expected a "
                f"verify mode from {VERIFY_MODES}, a ResilienceOptions, "
                f"or a dict of its fields")
        return ResilienceOptions(verify=resilience)
    if isinstance(resilience, dict):
        return ResilienceOptions(**resilience)
    raise ValueError(f"cannot interpret resilience={resilience!r}")


@dataclasses.dataclass(frozen=True)
class Attempt:
    """One ladder step (telemetry row of the DegradationReport)."""

    rung: str                     # transport rung (or "refit")
    algorithm: str                # schedule/algorithm attempted
    attempt: int                  # 0-based retry index within the rung
    outcome: str                  # ok|fault|timeout|corrupt|skipped
    detail: str = ""
    seconds: float = 0.0


@dataclasses.dataclass
class DegradationReport:
    """What the ladder did for one call: every attempt, every checksum
    verdict, where (if anywhere) recovery landed."""

    schedule: str
    verify: str
    attempts: list = dataclasses.field(default_factory=list)
    verdicts: list = dataclasses.field(default_factory=list)
    recovered_with: str | None = None    # rung that produced the output
    refit_algorithm: str | None = None   # set when the refit rung won

    @property
    def degraded(self) -> bool:
        """True when the call did not succeed first-try on the first
        available rung."""
        return (self.refit_algorithm is not None
                or any(a.outcome not in ("ok", "skipped")
                       for a in self.attempts))

    @property
    def retries(self) -> int:
        return sum(1 for a in self.attempts
                   if a.outcome in ("fault", "timeout", "corrupt"))

    def summary(self) -> str:
        path = " -> ".join(f"{a.rung}[{a.outcome}]" for a in self.attempts)
        return (f"{self.schedule}: {path}; recovered_with="
                f"{self.recovered_with} refit={self.refit_algorithm}")


def announce(report: DegradationReport) -> None:
    """Print a ladder walk that left its first rung: a descent is never
    silent, even when the result is correct."""
    if report.degraded:
        print(f"recovery ladder descended: {report.summary()}",
              file=sys.stderr, flush=True)


class UnrecoverableError(RuntimeError):
    """Every rung and every refit candidate failed; the attached
    ``report`` records the full ladder walk."""

    def __init__(self, msg: str, report: DegradationReport):
        super().__init__(msg + " | " + report.summary())
        self.report = report


def canary_pattern(schedule: CommSchedule, dtype, slot_shape) -> np.ndarray:
    """Deterministic per-rank canary rows [nranks, 1, *slot] — seeded by
    the schedule fingerprint so replays and reports agree."""
    digest = hashlib.sha1(
        ("canary:" + schedule.fingerprint()).encode()).digest()
    rng = np.random.default_rng(int.from_bytes(digest[:8], "little"))
    shape = (schedule.nranks, 1) + tuple(slot_shape)
    dt = np.dtype(dtype)
    vals = rng.integers(1, 100, size=shape)
    if not np.issubdtype(dt, np.integer):
        vals = vals.astype(np.float64)
    return np.asarray(vals).astype(dt)


def _checksum(buf) -> str:
    a = np.ascontiguousarray(np.asarray(buf))
    return hashlib.sha1(a.tobytes()).hexdigest()


class ResilientExec:
    """The recovery-ladder engine for one compiled schedule.

    Host-level: ``run(gbuf)`` takes a concrete global
    [nranks, num_slots, *slot] buffer (the SimTransport /
    ``run_global`` calling convention every bit-exactness sweep
    drives) and returns ``(output, DegradationReport)``.

    ``transports`` optionally overrides rung construction with
    ready-made transport instances — the chaos tests inject
    ``chaos.wrap``-ped rungs there; anything not overridden is built
    clean.  ``collective``/``algorithm`` name the plan for the refit
    rung (omit them and refit is skipped).
    """

    def __init__(self, schedule: CommSchedule, topo: Topology | None = None,
                 *, options: ResilienceOptions | None = None,
                 collective: str | None = None,
                 algorithm: str | None = None,
                 transports: dict | None = None):
        self.schedule = schedule
        self.topo = topo
        self.options = options or ResilienceOptions()
        self.collective = collective
        self.algorithm = algorithm
        self.transports = dict(transports or {})
        self._canary: CommSchedule | None = None

    # -- rung plumbing ----------------------------------------------------
    def _transport(self, rung: str):
        tr = self.transports.get(rung)
        if tr is not None:
            return tr
        n = self.schedule.nranks
        if rung == "pallas":
            return PallasTransport(n, topo=self.topo)
        if rung == "shardmap":
            return ShardMapTransport(n, "_resil", topo=self.topo)
        return SimTransport(n, topo=self.topo)     # sim | reference

    def _rung_unavailable(self, rung: str) -> str | None:
        if rung == "shardmap" and "shardmap" not in self.transports \
                and jax.device_count() < self.schedule.nranks:
            return (f"needs {self.schedule.nranks} devices, have "
                    f"{jax.device_count()}")
        return None

    def _call(self, rung: str, schedule: CommSchedule, buf):
        tr = self._transport(rung)
        if rung == "pallas":
            out = tr.run_global(schedule, buf)
        elif rung == "shardmap":
            out = tr.run_global(schedule, buf)
        elif rung == "reference":
            out = tr.run_reference(schedule, buf)
        else:
            out = tr.run(schedule, buf)
        return jax.block_until_ready(out) if hasattr(out, "block_until_ready") \
            else out

    # -- verification -----------------------------------------------------
    def _result_region(self, schedule: CommSchedule, out) -> np.ndarray:
        a = np.asarray(out)
        rows = schedule.result_slots
        return np.stack([a[r, schedule.out_offset(r):
                           schedule.out_offset(r) + rows]
                         for r in range(schedule.nranks)])

    def _verify(self, report, schedule, out, *, pattern, in_sum, buf,
                in_finite, reference) -> bool:
        """All verdicts are bitwise (``tobytes`` compares) so NaN-vs-NaN
        and negative-zero cases are never misjudged; ``schedule`` is the
        ORIGINAL (canary-free) schedule whose geometry defines the
        result region and the canary row index."""
        opts = self.options
        out = np.asarray(out)
        ok = True
        if pattern is not None:
            got = np.ascontiguousarray(
                out[:, schedule.num_slots: schedule.num_slots + 1])
            canary_ok = got.tobytes() == np.asarray(pattern).tobytes()
            report.verdicts.append(("canary", canary_ok))
            ok &= canary_ok
        if in_sum is not None:
            input_ok = _checksum(buf) == in_sum
            report.verdicts.append(("input-checksum", input_ok))
            ok &= input_ok
        res = self._result_region(schedule, out)
        if in_finite and np.issubdtype(res.dtype, np.floating):
            finite_ok = bool(np.isfinite(
                res.astype(np.float32, copy=False)).all())
            report.verdicts.append(("finite", finite_ok))
            ok &= finite_ok
        if opts.verify == "full":
            ref_ok = (np.ascontiguousarray(res).tobytes()
                      == np.ascontiguousarray(reference).tobytes())
            report.verdicts.append(("reference", ref_ok))
            ok &= ref_ok
        return ok

    # -- the ladder -------------------------------------------------------
    def run(self, buf):
        """Execute with the full recovery ladder; returns
        ``(output, DegradationReport)`` or raises a typed
        ``UnrecoverableError``."""
        opts = self.options
        report = DegradationReport(schedule=self.schedule.name,
                                   verify=opts.verify)
        out = self._run_ladder(buf, report, self.schedule,
                               self.algorithm or self.schedule.name)
        if out is not None:
            announce(report)
            return out, report
        # every rung failed -> algorithm refit (selector NotApplicable
        # ladder, the PR 8 elastic-swap machinery)
        if opts.refit and self.collective is not None \
                and self.topo is not None:
            from repro.core.algorithms import REGISTRY
            from repro.core.selector import _FIXED
            coll = self.collective
            ladder = [a for a in _FIXED.get(coll, ())
                      if a != self.algorithm]
            ladder += [a for a in REGISTRY.get(coll, {})
                       if a != self.algorithm and a not in ladder]
            for cand in ladder:
                try:
                    cand_sched = REGISTRY[coll][cand](self.topo)
                except NotApplicable as e:
                    report.attempts.append(Attempt(
                        rung="refit", algorithm=cand, attempt=0,
                        outcome="skipped", detail=str(e) or "NotApplicable"))
                    continue
                child = ResilientExec(
                    cand_sched, self.topo, options=opts,
                    collective=None, algorithm=cand,
                    transports=self.transports)
                child_report = DegradationReport(
                    schedule=cand_sched.name, verify=opts.verify)
                out = child._run_ladder(buf, child_report, cand_sched, cand)
                report.attempts.extend(child_report.attempts)
                report.verdicts.extend(child_report.verdicts)
                if out is not None:
                    report.refit_algorithm = cand
                    report.recovered_with = child_report.recovered_with
                    announce(report)
                    return out, report
        raise UnrecoverableError(
            "collective could not be recovered on any transport rung "
            "or refit algorithm", report)

    def _run_ladder(self, buf, report, schedule, algorithm):
        """Walk the transport rungs for ONE schedule; returns the
        verified output (canary stripped) or None when every rung is
        exhausted."""
        opts = self.options
        use_canary = opts.verify != "off"
        pattern = in_sum = None
        xsched, xbuf = schedule, buf
        if use_canary:
            if schedule is self.schedule:
                if self._canary is None:
                    self._canary = add_canary_slot(schedule)
                xsched = self._canary
            else:
                xsched = add_canary_slot(schedule)
            pattern = canary_pattern(schedule, np.asarray(buf).dtype,
                                     np.asarray(buf).shape[2:])
            xbuf = np.concatenate([np.asarray(buf), pattern], axis=1)
            in_sum = _checksum(xbuf)
        in_finite = bool(np.isfinite(
            np.asarray(buf).astype(np.float32, copy=False)).all()) \
            if np.issubdtype(np.asarray(buf).dtype, np.floating) else False
        reference = None
        if opts.verify == "full":
            ref_tr = SimTransport(schedule.nranks, topo=self.topo)
            reference = self._result_region(
                schedule, ref_tr.run_reference(schedule, np.asarray(buf)))
        return self._walk(report, schedule, xsched, xbuf, algorithm,
                          pattern=pattern, in_sum=in_sum,
                          in_finite=in_finite, reference=reference)

    def _walk(self, report, schedule, xsched, xbuf, algorithm, *,
              pattern, in_sum, in_finite, reference):
        opts = self.options
        for rung in opts.ladder:
            reason = self._rung_unavailable(rung)
            if reason is not None:
                report.attempts.append(Attempt(
                    rung=rung, algorithm=algorithm, attempt=0,
                    outcome="skipped", detail=reason))
                continue
            delay = opts.backoff_s
            for attempt in range(opts.max_retries + 1):
                t0 = time.perf_counter()
                try:
                    out = self._call(rung, xsched, xbuf)
                except TransportError as e:
                    report.attempts.append(Attempt(
                        rung=rung, algorithm=algorithm, attempt=attempt,
                        outcome="fault", detail=str(e),
                        seconds=time.perf_counter() - t0))
                    time.sleep(delay)
                    delay *= opts.backoff_mult
                    continue
                dt = time.perf_counter() - t0
                if opts.deadline_s is not None and dt > opts.deadline_s:
                    report.attempts.append(Attempt(
                        rung=rung, algorithm=algorithm, attempt=attempt,
                        outcome="timeout",
                        detail=f"{dt:.4f}s > deadline "
                               f"{opts.deadline_s:.4f}s", seconds=dt))
                    time.sleep(delay)
                    delay *= opts.backoff_mult
                    continue
                if self._verify(report, schedule, out, pattern=pattern,
                                in_sum=in_sum, buf=xbuf,
                                in_finite=in_finite, reference=reference):
                    report.attempts.append(Attempt(
                        rung=rung, algorithm=algorithm, attempt=attempt,
                        outcome="ok", seconds=dt))
                    report.recovered_with = rung
                    a = np.asarray(out)
                    return a[:, :schedule.num_slots] if pattern is not None \
                        else a
                report.attempts.append(Attempt(
                    rung=rung, algorithm=algorithm, attempt=attempt,
                    outcome="corrupt", detail="integrity check failed",
                    seconds=dt))
                time.sleep(delay)
                delay *= opts.backoff_mult
        return None


def run_resilient(schedule: CommSchedule, buf, *,
                  topo: Topology | None = None,
                  resilience=True, collective: str | None = None,
                  algorithm: str | None = None,
                  transports: dict | None = None):
    """One-shot convenience: build a ``ResilientExec`` and run it."""
    opts = resolve_resilience(resilience) or ResilienceOptions()
    ex = ResilientExec(schedule, topo, options=opts,
                       collective=collective, algorithm=algorithm,
                       transports=transports)
    return ex.run(buf)
