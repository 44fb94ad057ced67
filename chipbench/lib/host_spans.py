"""The program's own spans and scopes in a profiler trace.

The serving engine (``src/repro/serving/engine.py``) writes ``serve.*``
host spans with ``jax.profiler.TraceAnnotation``; they land on a thread
line of a ``/host:`` plane, with their kwargs as stats, on the device
planes' clock.  The model wraps its layers in ``jax.named_scope``, which
XLA keeps as each instruction's ``op_name`` metadata.  A TPU trace
carries it as the ``tf_op`` stat of the event *metadata* of each
operation on ``/device:TPU:0``'s ``XLA Ops`` line (for example
``jit(serve_decode)/attention/dot_general``; a fusion carries one
instruction's), beside ``hlo_category``, ``flops``, ``bytes_accessed`` and
``source``; the event itself holds only its device times.
``jax.profiler.ProfileData`` does not show metadata stats, so this module
reads the trace file with protobuf, by the subset of XSpace's schema
(``tsl/profiler/protobuf/xplane.proto``) built in ``_xspace``.  The
engine jits its two programs as ``serve_prefill`` and ``serve_decode``,
so their executions on the ``XLA Modules`` line are named
``jit_serve_prefill(<fingerprint>)`` and ``jit_serve_decode(...)``.

A trace of a program without these (an older commit) reads as no spans,
no scopes and no named programs, and the readers built on this return
``None``.

    python3 -m chipbench.lib.host_spans <run.xplane.pb>

prints a summary of one trace: the spans by name, the idle time by the
innermost ``serve.*`` span, and the host events inside the longest
``serve.decode`` and ``serve.slot_write`` spans.
"""
from __future__ import annotations

import bisect
import json
import sys
from dataclasses import dataclass
from functools import lru_cache

from chipbench.lib import trace as tr

PREFIX = "serve."
SCOPE_STAT = "tf_op"
DECODE_PROGRAM = "jit_serve_decode("
LAYER_SCOPES = ("embed", "attention", "mamba", "mlstm", "slstm", "norm",
                "mlp", "moe", "mhc", "lm_head")


@dataclass
class Op:
    """One operation on the chip: its scope path and its self time, the
    duration less the part that operations nested in it cover."""
    name: str
    start: float        # ns
    end: float
    scope: str
    self_ns: float


@dataclass
class Spans:
    spans: list[tr.Event]       # serve.* host spans, by start
    modules: list[tr.Event]     # program executions on chip 0
    ops: list[Op]               # operations on chip 0, by start
    host: list[tr.Event]        # every host event, by start

    def __post_init__(self):
        self.op_starts = [o.start for o in self.ops]


@lru_cache(maxsize=2)
def load(path) -> Spans:
    """The spans, programs and operations of the trace file ``path``."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return reduce(space)


def reduce(space) -> Spans:
    spans, host, modules, ops = [], [], [], []
    for plane in space.planes:
        smeta = {k: v.name for k, v in plane.stat_metadata.items()}
        emeta = plane.event_metadata
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e, (s, t) in zip(ln.events, _times(ln)):
                    ev = tr.Event(emeta[e.metadata_id].name, s, t)
                    if ev.name.startswith(PREFIX):
                        ev.stats = _stats(e.stats, smeta)
                        spans.append(ev)
                    host.append(ev)
        elif plane.name == "/device:TPU:0":
            scope = {k: str(_stats(md.stats, smeta).get(SCOPE_STAT, ""))
                     for k, md in emeta.items()}
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    modules = sorted(
                        (tr.Event(emeta[e.metadata_id].name, s, t)
                         for e, (s, t) in zip(ln.events, _times(ln))),
                        key=lambda m: m.start)
                elif ln.name == "XLA Ops":
                    ops = self_times(
                        (emeta[e.metadata_id].name, s, t,
                         scope[e.metadata_id])
                        for e, (s, t) in zip(ln.events, _times(ln)))
    spans.sort(key=lambda e: (e.start, -e.end))
    host.sort(key=lambda e: e.start)
    return Spans(spans, modules, ops, host)


def _times(line):
    """(start, end) in ns of each event of a line, as ``ProfileData``
    gives them."""
    t0 = line.timestamp_ns
    for e in line.events:
        start = t0 + e.offset_ps // 1000
        yield float(start), float(start + e.duration_ps // 1000)


def _stats(stats, smeta) -> dict:
    out = {}
    for st in stats:
        kind = st.WhichOneof("value")
        v = getattr(st, kind) if kind else None
        out[smeta.get(st.metadata_id, "")] = \
            smeta.get(v, v) if kind == "ref_value" else v
    return out


@lru_cache(maxsize=1)
def _xspace():
    """The message class of an XSpace, with the fields read here; protobuf
    skips the others."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    pkg = "chipbench.xplane"
    f = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package=pkg, syntax="proto3")

    def message(name, fields, within=None):
        """Scalar fields (name, number, type) and repeated message fields
        (name, number, message name)."""
        m = (within or f.message_type).add(name=name)
        if name == "XStat":
            m.oneof_decl.add(name="value")
        for fname, number, kind in fields:
            if isinstance(kind, str):
                m.field.add(name=fname, number=number, type=F.TYPE_MESSAGE,
                            label=F.LABEL_REPEATED,
                            type_name=f".{pkg}.{kind}")
            else:
                fd = m.field.add(name=fname, number=number, type=kind,
                                 label=F.LABEL_OPTIONAL)
                if fname.endswith("_value"):
                    fd.oneof_index = 0
        return m

    message("XStat", [("metadata_id", 1, F.TYPE_INT64),
                      ("double_value", 2, F.TYPE_DOUBLE),
                      ("uint64_value", 3, F.TYPE_UINT64),
                      ("int64_value", 4, F.TYPE_INT64),
                      ("str_value", 5, F.TYPE_STRING),
                      ("bytes_value", 6, F.TYPE_BYTES),
                      ("ref_value", 7, F.TYPE_UINT64)])
    message("XEvent", [("metadata_id", 1, F.TYPE_INT64),
                       ("offset_ps", 2, F.TYPE_INT64),
                       ("duration_ps", 3, F.TYPE_INT64),
                       ("stats", 4, "XStat")])
    message("XLine", [("name", 2, F.TYPE_STRING),
                      ("timestamp_ns", 3, F.TYPE_INT64),
                      ("events", 4, "XEvent")])
    message("XEventMetadata", [("name", 2, F.TYPE_STRING),
                               ("stats", 5, "XStat")])
    message("XStatMetadata", [("name", 2, F.TYPE_STRING)])
    plane = message("XPlane", [("name", 2, F.TYPE_STRING),
                               ("lines", 3, "XLine"),
                               ("event_metadata", 4,
                                "XPlane.EventMetadataEntry"),
                               ("stat_metadata", 5,
                                "XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = plane.nested_type.add(name=entry)
        e.options.map_entry = True
        e.field.add(name="key", number=1, type=F.TYPE_INT64,
                    label=F.LABEL_OPTIONAL)
        e.field.add(name="value", number=2, type=F.TYPE_MESSAGE,
                    label=F.LABEL_OPTIONAL, type_name=f".{pkg}.{value}")
    message("XSpace", [("planes", 1, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


def self_times(events) -> list[Op]:
    """``Op``s of (name, start, end, scope) events on one line, where an
    event may hold others (a ``while`` and its body): each gets its
    duration less what the events directly inside it cover."""
    ops = [Op(n, s, e, sc, e - s) for n, s, e, sc in events]
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: list[Op] = []
    for o in ops:
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack:
            parent = stack[-1]
            parent.self_ns -= min(o.end, parent.end) - o.start
        stack.append(o)
    return ops


def in_window(spans, name: str, t0: float, t1: float) -> list[tr.Event]:
    return [s for s in spans if s.name == name and s.start >= t0
            and s.end <= t1]


def durations_ms(spans, name: str, t0: float, t1: float) -> list[float]:
    return [(s.end - s.start) / 1e6 for s in in_window(spans, name, t0, t1)]


def overlap_ns(a, b) -> float:
    """Length of the intersection of two lists of disjoint, sorted
    (start, end) intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def ops_of(sp: Spans, module: tr.Event) -> list[Op]:
    """The operations that ran inside one program execution."""
    lo = bisect.bisect_left(sp.op_starts, module.start)
    hi = bisect.bisect_left(sp.op_starts, module.end)
    return [o for o in sp.ops[lo:hi] if o.end <= module.end]


def scope_ns(sp: Spans, module: tr.Event, scope: str) -> float:
    """Self time of the operations inside one program execution whose
    scope path holds ``scope`` (``attention``)."""
    part = f"/{scope}/"
    return sum(o.self_ns for o in ops_of(sp, module)
               if part in "/" + o.scope)


def idle_by_span(spans, gaps) -> dict[str, float]:
    """The idle time of ``gaps`` ((start, end), ns), each instant put
    down to the innermost ``serve.*`` span that holds it, or ``none``."""
    out: dict[str, float] = {}
    starts = [s.start for s in spans]
    for gs, ge in gaps:
        segs = [[gs, ge, "none"]]
        for sp in spans[:bisect.bisect_left(starts, ge)]:
            if sp.end <= gs:
                continue
            nxt = []
            for s, e, name in segs:
                lo, hi = max(s, sp.start), min(e, sp.end)
                if hi <= lo:
                    nxt.append([s, e, name])
                    continue
                nxt += [seg for seg in ([s, lo, name], [lo, hi, sp.name],
                                        [hi, e, name]) if seg[1] > seg[0]]
            segs = nxt
        for s, e, name in segs:
            out[name] = out.get(name, 0.0) + (e - s)
    return out


def host_events_inside(sp: Spans, span: tr.Event, n: int = 12):
    """The longest host events inside ``span`` other than ``serve.*``
    spans, as [name, ms, count]."""
    total: dict[str, list] = {}
    for e in sp.host:
        if e.start > span.end:
            break
        if e.start >= span.start and e.end <= span.end and \
                not e.name.startswith(PREFIX):
            t = total.setdefault(e.name, [0.0, 0])
            t[0] += (e.end - e.start) / 1e6
            t[1] += 1
    rows = sorted(total.items(), key=lambda kv: -kv[1][0])[:n]
    return [[k, v[0], v[1]] for k, v in rows]


def summary(path) -> dict:
    """Per ``serve.*`` span: count, median and total ms in the traced
    window (the window of ``trace.reduce``, else the spans' own extent);
    idle ms by innermost span; the host events inside the longest
    ``serve.decode`` and ``serve.slot_write``; device ms per program and
    per scope of the decode program."""
    from chipbench.lib.context import median
    t = tr.load(path)
    sp = load(path)
    t0, t1 = t.t0, t.t1
    if t1 <= t0 and sp.spans:
        t0, t1 = sp.spans[0].start, max(s.end for s in sp.spans)
    per_span = {}
    for name in sorted({s.name for s in sp.spans}):
        d = durations_ms(sp.spans, name, t0, t1)
        per_span[name] = {"count": len(d), "median_ms": median(d),
                          "total_ms": sum(d)}
    gaps = tr.idle_gaps(t.devices[0], t0, t1) if t.devices else []
    inside = {}
    for name in (PREFIX + "decode", PREFIX + "slot_write"):
        cands = in_window(sp.spans, name, t0, t1)
        if cands:
            longest = max(cands, key=lambda s: s.end - s.start)
            inside[name] = {"ms": (longest.end - longest.start) / 1e6,
                            "events": host_events_inside(sp, longest)}
    modules = [m for m in sp.modules if m.start >= t0 and m.end <= t1]
    programs: dict[str, list] = {}
    for m in modules:
        p = programs.setdefault(m.name.split("(", 1)[0], [0, 0.0])
        p[0] += 1
        p[1] += (m.end - m.start) / 1e6
    scopes: dict[str, float] = {}
    decodes = [m for m in modules if m.name.startswith(DECODE_PROGRAM)]
    for m in decodes:
        for o in ops_of(sp, m):
            key = next((p for p in o.scope.split("/") if p in LAYER_SCOPES),
                       "other")
            scopes[key] = scopes.get(key, 0.0) + o.self_ns / 1e6
    return {"window_ms": (t1 - t0) / 1e6,
            "idle_ms": sum(e - s for s, e in gaps) / 1e6,
            "spans": per_span,
            "idle_by_span_ms": {k: v / 1e6 for k, v in
                                idle_by_span(sp.spans, gaps).items()},
            "inside_longest": inside,
            "programs": programs,
            "decode_scope_ms_per_step": {
                k: v / max(1, len(decodes)) for k, v in scopes.items()}}


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1]), indent=1))
