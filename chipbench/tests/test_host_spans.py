"""The readers of the program's own spans and scopes on small synthetic
traces: ``serve.*`` host spans with their stats, named programs, device
operations whose event metadata carries a scope path in its ``tf_op``
stat (as a TPU trace has it), and a ``while`` that holds its body."""
import json

import pytest

from chipbench.lib import host_spans as hs
from chipbench.lib import trace as tr
from chipbench.lib.context import RunContext
from chipbench.run import reader
from chipbench.tests.test_work import CONF

NEW = ("decode_dispatch_ms", "slot_write_ms", "idle_admit.serve",
       "decode_attention_ms")
DEC, PRE = "jit(serve_decode)", "jit(serve_prefill)"
# (line, name, start ns, end ns, stats); one chip, one host thread
EVENTS = [
    ("host", "serve.step", 0, 30000, {"step": 0}),
    ("host", "serve.admit", 100, 12000, {"uid": 7, "slot": 0,
                                         "tokens": 512}),
    ("host", "cb.admit", 150, 11900, {}),
    ("host", "serve.prefill", 200, 1200, {"tokens": 512}),
    ("host", "cb.prefill", 250, 1150, {"tokens": 512}),
    ("host", "serve.slot_write", 1300, 5300, {"leaves": 4}),
    ("host", "serve.first_token", 5400, 11800, {}),
    ("host", "serve.decode", 13000, 14000, {"active": 1}),
    ("host", "cb.decode", 13100, 13900, {}),
    ("host", "serve.decode_sync", 14000, 24500, {}),
    ("host", "serve.emit", 24500, 25000, {"done": 0}),
    ("host", "serve.step", 30000, 50000, {"step": 1}),
    ("host", "serve.decode", 30100, 32100, {"active": 1}),
    ("host", "cb.decode", 30200, 32000, {}),
    ("host", "serve.decode_sync", 32100, 45000, {}),
    ("host", "serve.emit", 45000, 45500, {"done": 1}),
    ("XLA Modules", "jit_serve_prefill(1)", 1000, 11000, {}),
    ("XLA Ops", "%fusion.1", 1000, 10000,
     {"tf_op": f"{PRE}/attention/dot_general"}),
    ("XLA Modules", "jit_serve_decode(2)", 14500, 24000, {}),
    ("XLA Ops", "%while.1", 14500, 22000, {"tf_op": f"{DEC}/while"}),
    ("XLA Ops", "%fusion.2", 15000, 17000,
     {"tf_op": f"{DEC}/attention/dot_general"}),
    ("XLA Ops", "%fusion.3", 18000, 21000,
     {"tf_op": f"{DEC}/mlp/dot_general"}),
    ("XLA Ops", "%fusion.4", 22000, 23500,
     {"tf_op": f"{DEC}/lm_head/dot_general"}),
    ("XLA Modules", "jit_serve_decode(2)", 33000, 43000, {}),
    ("XLA Ops", "%fusion.5", 33000, 37000,
     {"tf_op": f"{DEC}/attention/convert_multiply"}),
    ("XLA Ops", "%fusion.6", 37000, 38000,
     {"tf_op": f"{DEC}/attention/dot_general"}),
    ("XLA Ops", "%fusion.7", 38000, 42000,
     {"tf_op": f"{DEC}/mlp/dot_general"}),
]
# the traced window: from the first device event or cb.* span (cb.admit)
# to the last device event
T0, T1 = 150, 43000


def text_proto(events):
    """An XSpace in text form: host events carry their stats, device
    operations carry theirs in their event metadata."""
    names = sorted({e[1] for e in events})
    meta = {n: i + 1 for i, n in enumerate(names)}
    stat_names = sorted({k for e in events for k in e[4]})
    smeta = {n: i + 1 for i, n in enumerate(stat_names)}
    md_stats = {e[1]: e[4] for e in events if e[0] == "XLA Ops"}

    def stat(k, v):
        value = f'str_value: {json.dumps(v)}' if isinstance(v, str) \
            else f"int64_value: {v}"
        return f"stats {{ metadata_id: {smeta[k]} {value} }} "

    def line(lid, name, evs):
        body = "".join(
            f"events {{ metadata_id: {meta[n]} offset_ps: {s * 1000} "
            f"duration_ps: {(e - s) * 1000} "
            + "".join(stat(k, v) for k, v in st.items()
                      if n not in md_stats) + "} "
            for _, n, s, e, st in evs)
        return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0 {body}}}'

    def metadata():
        return " ".join(
            [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
             + "".join(stat(k, v) for k, v in md_stats.get(n, {}).items())
             + "} }"
             for n, i in meta.items()]
            + [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
               for n, i in smeta.items()])

    dev = [e for e in events if e[0] != "host"]
    host = [e for e in events if e[0] == "host"]
    return (
        f'planes {{ id: 1 name: "/device:TPU:0" '
        f'{line(1, "XLA Modules", [e for e in dev if e[0] == "XLA Modules"])}'
        f'{line(2, "XLA Ops", [e for e in dev if e[0] == "XLA Ops"])}'
        f'{metadata()} }} '
        f'planes {{ id: 2 name: "/host:CPU" {line(3, "main/1", host)} '
        f'{metadata()} }}')


def write_trace(path, events):
    from jax.profiler import ProfileData
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        text_proto(events)))
    return str(path)


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    return write_trace(tmp_path_factory.mktemp("t") / "run.xplane.pb",
                       EVENTS)


def context(path):
    return RunContext({"conf": CONF}, {"bf16_flops_per_s": 197e12,
                                       "hbm_bytes_per_s": 819e9}, path)


def test_spans_carry_their_stats(xplane):
    sp = hs.load(xplane)
    assert [s.name for s in sp.spans][:4] == [
        "serve.step", "serve.admit", "serve.prefill", "serve.slot_write"]
    admit = sp.spans[1]
    assert admit.stats == {"uid": 7, "slot": 0, "tokens": 512}
    assert [m.name for m in sp.modules] == [
        "jit_serve_prefill(1)", "jit_serve_decode(2)", "jit_serve_decode(2)"]


def test_scope_path_is_read_from_the_op_stat(xplane):
    ops = {o.name: o for o in hs.load(xplane).ops}
    assert ops["%fusion.2"].scope == f"{DEC}/attention/dot_general"
    assert ops["%fusion.4"].scope == f"{DEC}/lm_head/dot_general"


def test_while_self_time_leaves_out_its_body(xplane):
    ops = {o.name: o for o in hs.load(xplane).ops}
    assert ops["%while.1"].self_ns == 7500 - 2000 - 3000
    assert ops["%fusion.2"].self_ns == 2000
    assert ops["%fusion.4"].self_ns == 1500
    # an op that runs past the end of the one holding it takes from it
    # only the part inside it
    nested = hs.self_times([("w", 0, 10, ""), ("a", 2, 4, ""),
                            ("b", 8, 12, ""), ("c", 12, 13, "")])
    assert [o.self_ns for o in nested] == [10 - 2 - 2, 2, 4, 1]


@pytest.mark.parametrize("name,want", [
    # serve.decode spans of 1000 and 2000 ns
    ("decode_dispatch_ms", 1500 / 1e6),
    ("slot_write_ms", 4000 / 1e6),
    # idle [150, 1000] lies inside serve.admit [100, 12000]; idle
    # [11000, 14500] overlaps it by 1000; idle [24000, 33000] not at all
    ("idle_admit.serve", 100 * (850 + 1000) / (T1 - T0)),
    # attention self time: 2000 in the first decode, 4000 + 1000 in the
    # second
    ("decode_attention_ms", 3500 / 1e6),
])
def test_readers_on_hand_computed_values(xplane, name, want):
    assert reader(name).read(context(xplane)) == pytest.approx(want)


def test_idle_time_by_innermost_span(xplane):
    t = tr.load(xplane)
    assert (t.t0, t.t1) == (T0, T1)
    idle = hs.idle_by_span(hs.load(xplane).spans,
                           tr.idle_gaps(t.devices[0], t.t0, t.t1))
    assert idle == {"serve.admit": 50 + 200, "serve.prefill": 800,
                    "serve.first_token": 800,
                    "serve.step": 1000 + 5000 + 100,
                    "serve.decode": 1000 + 2000,
                    "serve.decode_sync": 500 + 500 + 900,
                    "serve.emit": 500}
    assert hs.idle_by_span([], [(0, 5)]) == {"none": 5}
    s = hs.summary(xplane)
    assert s["idle_by_span_ms"]["serve.decode"] == pytest.approx(3000 / 1e6)
    assert s["spans"]["serve.decode"]["count"] == 2
    assert s["decode_scope_ms_per_step"]["attention"] == \
        pytest.approx(7000 / 1e6 / 2)


def test_overlap_of_interval_lists():
    assert hs.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 5 + 5
    assert hs.overlap_ns([(0, 10)], []) == 0


def test_trace_reduce_keeps_only_the_harness_spans(xplane):
    t = tr.load(xplane)
    assert [s.name for s in t.spans] == ["cb.admit", "cb.prefill",
                                         "cb.decode", "cb.decode"]
    kinds = [x.kind for x in tr.executions(t, t.devices[0])]
    assert kinds == ["cb.prefill", "cb.decode", "cb.decode"]


def test_readers_without_serve_spans_return_nothing(tmp_path):
    """A trace of a program without the spans, scopes and named programs
    (an older commit): the new readers read nothing and do not raise."""
    old = [("host", "cb.decode", 100, 200, {}),
           ("XLA Modules", "jit__lambda(3)", 1000, 2000, {}),
           ("XLA Ops", "%fusion.7", 1000, 1900, {"tf_op": "jit(<lambda>)"})]
    path = write_trace(tmp_path / "old.xplane.pb", old)
    for name in NEW:
        assert reader(name).read(context(path)) is None
    untraced = context(None)
    for name in NEW:
        assert reader(name).read(untraced) is None
