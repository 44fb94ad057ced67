"""Reduce a profiler trace to device busy time, program executions, kernel
time and idle gaps named by what the host was doing.

What a TPU trace holds (``jax.profiler.ProfileData``): one plane per chip,
``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per program
execution, named ``<jit name>(<program fingerprint>)``) and a line ``XLA
Ops`` (one event per operation, named by its HLO instruction text).  The
host plane ``/host:CPU`` holds the harness's ``TraceAnnotation`` spans.
All times are nanoseconds on one clock.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

MOSAIC = 'custom_call_target="tpu_custom_call"'


@dataclass
class Event:
    name: str
    start: float        # ns
    end: float          # ns
    stats: dict = field(default_factory=dict)


@dataclass
class Device:
    modules: list[Event]
    ops: list[Event]


@dataclass
class Trace:
    devices: list[Device]
    spans: list[Event]          # host spans whose names start with "cb."
    t0: float                   # traced window, ns
    t1: float


def find_xplane(log_dir) -> str:
    files = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(files)}")
    return files[0]


def load(path) -> Trace:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(path)))


def reduce(pd) -> Trace:
    """The devices' programs and operations and the harness's host spans
    of a ``ProfileData``."""
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(Device(
                modules=_events(lines.get("XLA Modules")),
                ops=_events(lines.get("XLA Ops"))))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [e for e in _events(ln, with_stats=True)
                          if e.name.startswith("cb.")]
    starts = [e.start for d in devices for e in d.modules + d.ops] + \
        [e.start for e in spans]
    ends = [e.end for d in devices for e in d.modules + d.ops] + \
        [e.end for e in spans]
    spans.sort(key=lambda e: e.start)
    return Trace(devices, spans, min(starts, default=0.0),
                 max(ends, default=0.0))


def _events(line, with_stats=False) -> list[Event]:
    if line is None:
        return []
    out = []
    for e in line.events:
        stats = dict(e.stats) if with_stats else {}
        out.append(Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         stats))
    out.sort(key=lambda e: e.start)
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(dev: Device, t0: float, t1: float) -> float:
    """Time in [t0, t1] in which some program ran on the device."""
    return sum(min(e, t1) - max(s, t0)
               for s, e in union((m.start, m.end) for m in dev.modules)
               if e > t0 and s < t1)


def idle_gaps(dev: Device, t0: float, t1: float) -> list[tuple[float,
                                                                float]]:
    """The intervals of [t0, t1] in which no program ran on the device."""
    gaps, at = [], t0
    for s, e in union((m.start, m.end) for m in dev.modules):
        if s > at:
            gaps.append((at, min(s, t1)))
        at = max(at, e)
        if at >= t1:
            break
    if at < t1:
        gaps.append((at, t1))
    return [(s, e) for s, e in gaps if e > s]


def innermost_span(spans: list[Event], t: float) -> str:
    """The name of the innermost host span that holds time ``t``, or
    ``host`` (engine loop code outside every span)."""
    best = None
    for sp in spans:
        if sp.start > t:
            break
        if sp.end >= t and (best is None or sp.start >= best.start):
            best = sp
    return best.name if best is not None else "host"


@dataclass
class Execution:
    """One program execution, with the host span that launched it."""
    kind: str           # "cb.prefill", "cb.decode", or "other"
    module: Event
    span: Event | None
    ops: list[Event]


def executions(trace: Trace, dev: Device) -> list[Execution]:
    """Program executions on ``dev``.  The engine waits for the device at
    the end of every admission and decode step, so the first program to
    start after a ``cb.prefill`` or ``cb.decode`` span opens is the one
    that span launched; every other execution is ``other``."""
    launches = [sp for sp in trace.spans
                if sp.name in ("cb.prefill", "cb.decode")]
    mods, kinds, i = dev.modules, {}, 0
    for sp in launches:
        while i < len(mods) and mods[i].start < sp.start:
            i += 1
        if i < len(mods):
            kinds[i] = sp
    res, ops, j = [], dev.ops, 0
    for k, m in enumerate(mods):
        while j < len(ops) and ops[j].start < m.start:
            j += 1
        inside, jj = [], j
        while jj < len(ops) and ops[jj].end <= m.end:
            inside.append(ops[jj])
            jj += 1
        sp = kinds.get(k)
        res.append(Execution(sp.name if sp is not None else "other", m, sp,
                             inside))
    return res


def top_ops(dev: Device, t0: float, t1: float, n: int = 10):
    """The operations that took most device time in [t0, t1], by the
    instruction's name without its number (``%fusion.12`` ->
    ``fusion``), as [name, seconds]."""
    total: dict[str, float] = {}
    for e in dev.ops:
        if e.start >= t0 and e.end <= t1:
            key = _op_key(e.name)
            total[key] = total.get(key, 0.0) + (e.end - e.start)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


def _op_key(name: str) -> str:
    head = name.split(" = ", 1)[0].lstrip("%")
    base = head.split(".", 1)[0]
    if MOSAIC in name:
        return f"mosaic:{base}"
    return base


def named_gaps(trace: Trace, dev: Device, n: int = 10):
    """The longest idle gaps, each named by the innermost host span at its
    middle, as [name, seconds]."""
    gaps = sorted(idle_gaps(dev, trace.t0, trace.t1),
                  key=lambda g: g[0] - g[1])[:n]
    return [[innermost_span(trace.spans, (s + e) / 2), (e - s) / 1e9]
            for s, e in gaps]
