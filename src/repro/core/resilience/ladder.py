"""Guarded kernel resolution — the degradation ladder (DESIGN.md §14).

One corrupt cache entry, one builder exception or one mis-fused chain must
never take down a serving fleet: every kernel request resolves down an
explicit rung sequence, each rung strictly safer (and slower) than the one
above it::

    cached_tuned   tuner-picked (fused) artifact served via the cache
    regenerate     fresh build through the full pipeline, cache bypassed
    streaming      the op's registered ``<op>_streaming`` fallback builder
    sequential     the registry default — for chains, the verified
                   unfused sequential baseline
    eager          the task's pure-JAX/numpy reference; cannot fail

A rung that raises, returns a failed verdict, or exceeds its attempt/time
budget produces a structured :class:`DegradationEvent` and the resolver
descends.  Repeated failures quarantine the (task fingerprint, rung) pair
fleet-wide — later requests skip the known-bad rung instead of re-failing
on every call.  An optional first-call NaN/Inf sentinel probes the
resolved kernel at check shapes and demotes a mis-verified chain to its
sequential rung at runtime.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .faults import FaultInjected  # noqa: F401  (re-exported for callers)

RUNGS = ("cached_tuned", "regenerate", "streaming", "sequential", "eager")


@dataclass(frozen=True)
class DegradationEvent:
    """One rung that did not serve the request: what failed, why, and for
    which task (by name and by structural fingerprint)."""
    task: str
    fingerprint: str
    rung: str
    cause: str          # "error" | "verdict" | "quarantined" | "nan-sentinel" | "timeout"
    detail: str = ""

    def describe(self) -> Dict[str, str]:
        return {"task": self.task, "fingerprint": self.fingerprint[:16],
                "rung": self.rung, "cause": self.cause,
                "detail": self.detail[:160]}


# Fleet-wide event log: every resolver appends here too, so a bench or CI
# sweep can assert a clean run recorded ZERO degradations (the guard must
# never silently demote a healthy chain).
EVENT_LOG: List[DegradationEvent] = []


def drain_events() -> List[DegradationEvent]:
    out = list(EVENT_LOG)
    EVENT_LOG.clear()
    return out


class Quarantine:
    """Failure memory shared across resolvers: a (task fingerprint, rung)
    pair that failed ``threshold`` times is skipped fleet-wide instead of
    re-failing on every request."""

    def __init__(self, threshold: int = 3):
        self.threshold = int(threshold)
        self._failures: Dict[Tuple[str, str], int] = {}

    def note_failure(self, fingerprint: str, rung: str) -> int:
        key = (fingerprint, rung)
        self._failures[key] = self._failures.get(key, 0) + 1
        return self._failures[key]

    def blocked(self, fingerprint: str, rung: str) -> bool:
        return self._failures.get((fingerprint, rung), 0) >= self.threshold

    def entries(self) -> Dict[Tuple[str, str], int]:
        return dict(self._failures)

    def clear(self) -> None:
        self._failures.clear()


class PersistentQuarantine(Quarantine):
    """A quarantine table that survives process restarts.

    The failure table lives in a JSON file next to the artifact cache
    (:meth:`from_cache` puts it at ``<cache.root>/quarantine.json``), so
    a restarting fleet member skips known-bad (fingerprint, rung) pairs
    instead of re-failing its way down the ladder once per process.
    Entries carry a last-failure timestamp and EXPIRE after
    ``max_age_s`` (default 7 days) at load time — the bad build that
    earned the quarantine may be long fixed, and a stale table must not
    pin a healthy fused kernel to its eager floor forever.  Writes are
    atomic (temp file + rename); a corrupt or unreadable table loads as
    empty, matching the cache's self-healing posture.  ``clock`` is
    injectable (epoch-seconds convention — timestamps are compared
    across processes) so expiry tests stay deterministic."""

    def __init__(self, path, threshold: int = 3,
                 max_age_s: float = 7 * 24 * 3600.0,
                 clock: Optional[Callable[[], float]] = None):
        super().__init__(threshold)
        self.path = Path(path)
        self.max_age_s = float(max_age_s)
        self.clock = clock if clock is not None else time.time
        self._stamps: Dict[Tuple[str, str], float] = {}
        self._load()

    @classmethod
    def from_cache(cls, cache, **kw) -> "PersistentQuarantine":
        from ..tuning.cache import ArtifactCache
        c = ArtifactCache.resolve(cache)
        if c is None:
            raise ValueError(f"no cache to persist next to: {cache!r}")
        return cls(c.root / "quarantine.json", **kw)

    def _load(self) -> None:
        if not self.path.exists():
            return
        try:
            data = json.loads(self.path.read_text())
            rows = data.get("entries", ())
        except (ValueError, OSError, AttributeError):
            return                      # corrupt table: start empty
        now = self.clock()
        for row in rows:
            try:
                key = (str(row["fingerprint"]), str(row["rung"]))
                count = int(row["count"])
                updated = float(row["updated"])
            except (KeyError, TypeError, ValueError):
                continue                # malformed row: drop it
            if now - updated > self.max_age_s:
                continue                # stale entry: expired
            self._failures[key] = count
            self._stamps[key] = updated

    def _store(self) -> None:
        rows = [{"fingerprint": fp, "rung": rung, "count": n,
                 "updated": self._stamps.get((fp, rung), self.clock())}
                for (fp, rung), n in sorted(self._failures.items())]
        self.path.parent.mkdir(parents=True, exist_ok=True)
        tmp = self.path.with_name(self.path.name + ".tmp")
        tmp.write_text(json.dumps({"version": 1, "entries": rows},
                                  indent=1, sort_keys=True))
        os.replace(tmp, self.path)

    def note_failure(self, fingerprint: str, rung: str) -> int:
        n = super().note_failure(fingerprint, rung)
        self._stamps[(fingerprint, rung)] = self.clock()
        self._store()
        return n

    def clear(self) -> None:
        super().clear()
        self._stamps.clear()
        self._store()


# the default fleet-wide table (tests construct their own)
GLOBAL_QUARANTINE = Quarantine()


@dataclass
class Resolution:
    """A served kernel request: the rung it landed on, the generation
    result (None for the eager rung), every degradation recorded on the
    way down, and a runner callable."""
    task_name: str
    fingerprint: str
    rung: str
    result: Optional[Any]               # planner.GenResult or None
    events: Tuple[DegradationEvent, ...]
    runner: Callable = field(repr=False, default=None)

    def __call__(self, *arrays):
        return self.runner(*arrays)

    @property
    def degraded(self) -> bool:
        return bool(self.events)

    @property
    def verdict(self) -> str:
        """``ok`` (landed on the top applicable rung), ``quarantined``
        (pushed all the way to eager by quarantine skips) or
        ``degraded`` (landed lower than the top rung)."""
        if not self.events:
            return "ok"
        if self.rung == "eager" and any(e.cause == "quarantined"
                                        for e in self.events):
            return "quarantined"
        return "degraded"


class GuardedResolver:
    """Resolve kernel requests down the degradation ladder.

    ``cache``      — ArtifactCache (or resolvable value) for the top rung;
                     None skips ``cached_tuned``.
    ``tune``       — tune on the cached/regenerate rungs (the fused pick
                     for chain ops).
    ``verify``     — run Pass@1 verification per rung (a failed verdict
                     demotes).
    ``attempts``   — attempts per rung before descending.
    ``rung_timeout_s`` — after a failed attempt, stop retrying the rung
                     once this much wall time was spent in it.
    ``sentinel``   — probe the first call at check shapes for NaN/Inf and
                     demote to the sequential rung when it trips.
    ``quarantine`` — a :class:`Quarantine`; defaults to the process-wide
                     fleet table.
    """

    def __init__(self, cache=None, *, tune: bool = True,
                 verify: bool = True, tune_budget: int = 8,
                 attempts: int = 1, rung_timeout_s: Optional[float] = None,
                 sentinel: bool = False,
                 quarantine: Optional[Quarantine] = None,
                 rtol: float = 3e-4, atol: float = 2e-5):
        from ..tuning.cache import ArtifactCache
        self.cache = ArtifactCache.resolve(cache)
        self.tune = bool(tune)
        self.verify = bool(verify)
        self.tune_budget = int(tune_budget)
        self.attempts = max(1, int(attempts))
        self.rung_timeout_s = rung_timeout_s
        self.sentinel = bool(sentinel)
        self.quarantine = (quarantine if quarantine is not None
                           else GLOBAL_QUARANTINE)
        self.rtol, self.atol = rtol, atol

    # -- plumbing ----------------------------------------------------------
    @staticmethod
    def _fingerprint(task) -> str:
        from ..tuning.cache import _digest, task_fingerprint
        return _digest(task_fingerprint(task))

    def _rung_applicable(self, rung: str, task) -> bool:
        from ..planner import PLANNER_REGISTRY, fallback_op_for
        if rung == "cached_tuned":
            return self.cache is not None
        if rung == "streaming":
            return fallback_op_for(task.op) in PLANNER_REGISTRY
        return True

    def _run_rung(self, rung: str, task):
        """One generation attempt at ``rung``; returns a GenResult (the
        caller judges it) or raises."""
        from ..planner import fallback_op_for, generate
        if rung == "cached_tuned":
            return generate(task, tune=self.tune,
                            tune_budget=self.tune_budget,
                            cache=self.cache, verify=self.verify,
                            rtol=self.rtol, atol=self.atol)
        if rung == "regenerate":
            return generate(task, tune=self.tune,
                            tune_budget=self.tune_budget,
                            cache=None, verify=self.verify,
                            rtol=self.rtol, atol=self.atol)
        if rung == "streaming":
            stask = dataclasses.replace(task, op=fallback_op_for(task.op))
            return generate(stask, tune=False, cache=None,
                            verify=self.verify,
                            rtol=self.rtol, atol=self.atol)
        if rung == "sequential":
            return generate(task, tune=False, cache=None,
                            verify=self.verify,
                            rtol=self.rtol, atol=self.atol)
        raise ValueError(f"no generation rung named {rung!r}")

    @staticmethod
    def _result_failure(result, verify: bool) -> Optional[str]:
        if result is None or result.artifact is None:
            return f"no artifact: {getattr(result, 'error', '')}"
        if not result.comp_ok:
            return f"Comp@1 failed: {result.error}"
        if verify and not result.pass_ok:
            return f"Pass@1 failed: {result.error}"
        return None

    def _sentinel_trips(self, task, result) -> Optional[str]:
        """First-call NaN/Inf probe at check shapes.  Returns a detail
        string when the probe produced non-finite outputs from finite
        inputs; None when it passed or could not run (shape-pinned chain
        artifacts refuse foreign shapes — an inconclusive probe must not
        demote a healthy kernel)."""
        from ..planner import default_inputs
        inputs = default_inputs(task, task.check_shapes)
        arrays = [inputs[tp.name] for tp in task.input_specs]
        if not all(np.all(np.isfinite(a)) for a in arrays
                   if np.issubdtype(np.asarray(a).dtype, np.floating)):
            return None
        try:
            outs = result.artifact.entry(*arrays)
        except Exception:  # noqa: BLE001 — probe inconclusive, not a demotion
            return None
        outs = outs if isinstance(outs, (tuple, list)) else (outs,)
        for o in outs:
            o = np.asarray(o)
            if np.issubdtype(o.dtype, np.floating) and \
                    not np.all(np.isfinite(o)):
                return (f"non-finite outputs at check shapes "
                        f"({int(np.sum(~np.isfinite(o)))} elements)")
        return None

    # -- the ladder --------------------------------------------------------
    def resolve(self, task) -> Resolution:
        fp = self._fingerprint(task)
        events: List[DegradationEvent] = []

        def note(rung: str, cause: str, detail: str = ""):
            ev = DegradationEvent(task.name, fp, rung, cause, detail)
            events.append(ev)
            EVENT_LOG.append(ev)
            return ev

        for rung in RUNGS[:-1]:
            if not self._rung_applicable(rung, task):
                continue            # structurally inapplicable, not a failure
            if self.quarantine.blocked(fp, rung):
                note(rung, "quarantined",
                     f"{self.quarantine.threshold}+ prior failures")
                continue
            t0 = time.monotonic()
            failure = None
            for attempt in range(self.attempts):
                try:
                    result = self._run_rung(rung, task)
                    failure = self._result_failure(result, self.verify)
                except Exception as e:  # noqa: BLE001 — rung failure, descend
                    failure = f"{type(e).__name__}: {e}"
                if failure is None:
                    break
                if self.rung_timeout_s is not None and \
                        time.monotonic() - t0 > self.rung_timeout_s:
                    failure = f"timeout after attempt {attempt + 1}: {failure}"
                    note(rung, "timeout", failure)
                    break
            if failure is not None:
                if not events or events[-1].rung != rung:
                    note(rung, "error", failure)
                self.quarantine.note_failure(fp, rung)
                continue
            if self.sentinel and rung != "sequential":
                trip = self._sentinel_trips(task, result)
                if trip is not None:
                    note(rung, "nan-sentinel", trip)
                    self.quarantine.note_failure(fp, rung)
                    continue
            art = result.artifact
            return Resolution(
                task.name, fp, rung, result, tuple(events),
                runner=lambda *arrays: art.entry(*arrays))

        # the floor: the task's own reference — pure JAX/numpy, cannot fail
        return Resolution(task.name, fp, "eager", None, tuple(events),
                          runner=lambda *arrays: task.ref(*arrays))
