"""A whole run of a tiny serving cell on the CPU, the chip check skipped:
the result line's schema, and a cell, mix, driver, architecture and
metric that exist only as added files found by name."""
import json

import pytest

from chipbench import run
from chipbench.tests.tiny import CELL, TINY_CONF, TINY_MIX, TINY_OWN, \
    FakeDevice, make_root

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("root"))


@pytest.fixture(scope="module")
def plain(root):
    return run.run_cell(CELL, SEED, 1.5, False, root=root,
                        devices=[FakeDevice()])


@pytest.fixture(scope="module")
def traced(root):
    return run.run_cell(CELL, SEED, 1.5, True, root=root,
                        devices=[FakeDevice()])


def test_result_line_schema(plain):
    assert list(plain) == ["correct", "attempted", "failed", "metrics",
                           "device", "checks"]
    assert plain["correct"] is True and plain["failed"] == 0
    assert plain["attempted"] > 10
    assert set(plain["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                     "out_tok_s", "setup_s"}
    for m in plain["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert set(plain["device"]) == {"platform", "kind", "count",
                                    "memory_peak_bytes"}
    c = plain["checks"]["token_gap"]
    assert 0 <= c["value"] <= c["limit"]
    json.dumps(plain)


def test_traced_line_schema(traced):
    assert list(traced)[-1] == "checks" and "breakdown" in traced
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(traced["device"])
    assert traced["device"]["window_s"] > 0
    # on the CPU no chip plane exists: only the host-side readings remain
    assert set(traced["metrics"]) <= {m["name"] for m in
                                      run.load_spec()["per_layer"]}
    assert traced["metrics"]["window_compiles.serve"]["value"] == 0
    assert traced["correct"] is True


def test_added_mix_and_metric_are_found_by_name(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    # a mix of data alone: free prompt lengths and shared prefixes
    stub = dict(TINY_MIX, prompt={"median": 40, "sigma": 0.3, "min": 20,
                                  "max": 60}, prefix={"count": 2,
                                                      "length": 16})
    (root / "chipbench" / "mixes" / "stub.json").write_text(json.dumps(stub))
    (root / "chipbench" / "cells" / "tiny.stub.json").write_text(
        json.dumps(dict(TINY_OWN, clients=2)))
    (root / "chipbench" / "metrics" / "stub_tokens.py").write_text(
        'UNIT = "tokens"\n\n\ndef read(run):\n'
        '    return run.record["stats"].out_tokens\n')
    spec["workloads"].append({"name": "tiny.stub", "config": "tiny",
                              "traffic": "stub", "chips": 1, "why": "stub"})
    spec["end_to_end"].append({
        "name": "stub_tokens", "unit": "tokens", "better": "higher",
        "bound": 0.1, "source": "host_clock", "workloads": ["tiny.stub"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run.run_cell("tiny.stub", SEED, 1.0, False, root=root,
                       devices=[FakeDevice()])
    assert out["metrics"]["stub_tokens"]["value"] > 0
    assert "ttft_p90_ms" not in out["metrics"]
    assert out["correct"] is True


STUB_DRIVER = """
from chipbench.lib.timeline import Record, window_stats


def run(conf, mix, own, seed, seconds, arch, trace_dir=None, fault=None,
        t_start=None, controls=()):
    times = [[0.0, 0.5], [0.2, 0.6], [0.7, 0.9]]
    rec = Record(times=times, clients=2, completions=[0.5],
                 opened_at=0.2)
    return {"setup_s": 1.5, "window": (0.2, 1.2), "conf": conf,
            "record": rec, "stats": window_stats(rec, 1.0),
            "memory_peak_bytes": None, "checked": own["answers"],
            "checks": {"steps_off": 0.0}, "controls": {}}
"""


def test_added_driver_is_found_by_name(tmp_path):
    root = make_root(tmp_path)
    bench = root / "chipbench"
    (bench / "drivers" / "stub_kind.py").write_text(STUB_DRIVER)
    (bench / "mixes" / "stub_mix.json").write_text(
        json.dumps({"kind": "stub_kind"}))
    (bench / "cells" / "tiny.stub_mix.json").write_text(json.dumps(
        {"answers": 3, "limits": {"steps_off": {"limit": 0}}}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.stub_mix", "config": "tiny",
                              "traffic": "stub_mix", "chips": 1,
                              "why": "stub"})
    spec["end_to_end"] = [m for m in spec["end_to_end"]
                          if m["name"] == "setup_s"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run.run_cell("tiny.stub_mix", SEED, 1.0, False, root=root,
                       devices=[FakeDevice()])
    assert out["correct"] is True and out["attempted"] == 1
    assert out["metrics"] == {"setup_s": {"value": 1.5, "unit": "s"}}
    assert out["checks"] == {"steps_off": {"value": 0.0, "limit": 0.0}}

# an architecture module that hands every call on to archs/dense.py and
# notes its name in a file beside itself
STUB_ARCH = """
from pathlib import Path

from chipbench.run import load

_dense = load("archs", "dense", Path(__file__).resolve().parents[2])
CALLS = Path(__file__).with_suffix(".calls")
NAMES = ["arch_config", "make_params", "logits_at", "n_params",
         "prefill_attention", "decode_attention"]


def _noted(name):
    def call(*args, **kwargs):
        with CALLS.open("a") as f:
            f.write(name + "\\n")
        return getattr(_dense, name)(*args, **kwargs)
    return call


globals().update({name: _noted(name) for name in NAMES})
"""


def test_added_arch_is_found_by_name(tmp_path):
    root = make_root(tmp_path)
    bench = root / "chipbench"
    (bench / "archs" / "stub_arch.py").write_text(STUB_ARCH)
    (bench / "configs" / "tiny_stub.json").write_text(json.dumps(
        dict(TINY_CONF, name="tiny_stub", bench_arch="stub_arch")))
    (bench / "cells" / "tiny_stub.short.json").write_text(
        json.dumps(TINY_OWN))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_stub", "source": "tests",
                            "file": "chipbench/configs/tiny_stub.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"] = [{"name": "tiny_stub.short", "config": "tiny_stub",
                          "traffic": "short", "chips": 1, "why": "stub"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny_stub.short"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run.run_cell("tiny_stub.short", SEED, 1.5, True, root=root,
                       devices=[FakeDevice()])
    # the weights, the reference that decided `correct` and the work that
    # mfu.serve counts all came from the added module
    assert out["correct"] is True
    assert out["metrics"]["mfu.serve"]["value"] > 0
    calls = (bench / "archs" / "stub_arch.calls").read_text().split()
    assert set(calls) == {"arch_config", "make_params", "logits_at",
                          "n_params", "prefill_attention",
                          "decode_attention"}
