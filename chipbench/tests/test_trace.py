"""The trace reduction on a small synthetic trace: busy union, idle share,
programs attributed to the host spans that launched them, gaps named by
the host span they fell in, and the readers built on them."""
import pytest

from chipbench.lib import trace as tr
from chipbench.lib.context import RunContext
from chipbench.run import reader
from chipbench.tests.test_work import CONF, DENSE

MOSAIC_OP = ('%_run.1 = f32[512,128] custom-call(f32[512,128] %a), '
             'custom_call_target=\\"tpu_custom_call\\"')
# (line, name, start ns, end ns, stats); one chip, one host thread
EVENTS = [
    ("host", "cb.admit", 0, 12000, {}),
    ("host", "cb.prefill", 100, 200, {"tokens": 512}),
    ("XLA Modules", "jit__lambda(1)", 1000, 11000, {}),
    ("XLA Ops", MOSAIC_OP, 2000, 4000, {}),
    ("XLA Ops", MOSAIC_OP, 5000, 6000, {}),
    ("XLA Ops", "%fusion.3 = bf16[512,2048] fusion()", 6000, 10000, {}),
    ("XLA Modules", "jit__argmax(2)", 11500, 11600, {}),
    ("host", "cb.decode", 13000, 13100, {}),
    ("XLA Modules", "jit__lambda(3)", 14000, 24000, {}),
    ("XLA Ops", "%fusion.7 = bf16[16,2048] fusion()", 14000, 23000, {}),
    ("host", "cb.decode", 29000, 29100, {}),
    ("XLA Modules", "jit__lambda(3)", 30000, 40000, {}),
]


def text_proto(events):
    names = sorted({e[1] for e in events})
    meta = {n: i + 1 for i, n in enumerate(names)}

    def line(lid, name, evs):
        body = "".join(
            f"events {{ metadata_id: {meta[n]} offset_ps: {s * 1000} "
            f"duration_ps: {(e - s) * 1000} "
            + "".join(f"stats {{ metadata_id: 1 int64_value: {v} }} "
                      for v in st.values()) + "} "
            for _, n, s, e, st in evs)
        return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0 {body}}}'

    def metadata():
        return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in meta.items())

    dev = [e for e in events if e[0] != "host"]
    host = [e for e in events if e[0] == "host"]
    return (
        f'planes {{ id: 1 name: "/device:TPU:0" '
        f'{line(1, "XLA Modules", [e for e in dev if e[0] == "XLA Modules"])}'
        f'{line(2, "XLA Ops", [e for e in dev if e[0] == "XLA Ops"])}'
        f'{metadata()} }} '
        f'planes {{ id: 2 name: "/host:CPU" {line(3, "main/1", host)} '
        f'{metadata()} stat_metadata {{ key: 1 value {{ id: 1 '
        f'name: "tokens" }} }} }}')


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    return tr.reduce(ProfileData.from_text_proto(text_proto(EVENTS)))


@pytest.fixture
def run(trace):
    ctx = RunContext({"arch": DENSE, "conf": CONF},
                     {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    ctx.__dict__["trace"] = trace
    return ctx


def test_reduce_reads_planes(trace):
    assert len(trace.devices) == 1
    assert [s.name for s in trace.spans] == ["cb.admit", "cb.prefill",
                                             "cb.decode", "cb.decode"]
    assert (trace.t0, trace.t1) == (0, 40000)
    assert len(trace.devices[0].modules) == 4


def test_busy_union_and_idle(trace):
    dev = trace.devices[0]
    assert tr.busy_ns(dev, 0, 40000) == 10000 + 100 + 10000 + 10000
    assert tr.busy_ns(dev, 5000, 12000) == 6000 + 100
    assert tr.idle_gaps(dev, 0, 40000) == [(0, 1000), (11000, 11500),
                                           (11600, 14000), (24000, 30000)]
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_executions_follow_their_spans(trace):
    ex = tr.executions(trace, trace.devices[0])
    assert [x.kind for x in ex] == ["cb.prefill", "other", "cb.decode",
                                    "cb.decode"]
    assert ex[0].span.stats["tokens"] == 512
    assert len(ex[0].ops) == 3 and len(ex[2].ops) == 1


def test_gaps_named_by_host_span(trace):
    assert tr.named_gaps(trace, trace.devices[0]) == [
        ["host", 6000 / 1e9], ["host", 2400 / 1e9],
        ["cb.admit", 1000 / 1e9], ["cb.admit", 500 / 1e9]]


def test_top_ops_group_by_instruction(trace):
    assert tr.top_ops(trace.devices[0], 0, 40000) == [
        ["fusion", 13000 / 1e9], ["mosaic:_run", 3000 / 1e9]]


@pytest.mark.parametrize("name,want", [
    ("decode_host_gap_ms", 0.006),
    ("decode_step_ms", 0.01),
    ("prefill_ms_per_ktok", 0.01 / 0.512),
    ("idle_share.serve", 100 * 9900 / 40000),
    # bytes bound: (2*4 + 2*2) heads x 512 x 2 x 2 B x 2 layers at 819 GB/s,
    # over 3000 ns of Mosaic kernels
    ("flash_roofline", 100 * (2 * 12 * 512 * 2 * 2 / 819e9) / 3e-6),
])
def test_readers_on_the_trace(run, name, want):
    assert reader(name).read(run) == pytest.approx(want)


def test_readers_without_their_events_return_nothing():
    ctx = RunContext({"arch": DENSE, "conf": CONF},
                     {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    ctx.__dict__["trace"] = None
    for name in ("decode_host_gap_ms", "decode_step_ms",
                 "prefill_ms_per_ktok", "idle_share.serve",
                 "flash_roofline"):
        assert reader(name).read(ctx) is None
