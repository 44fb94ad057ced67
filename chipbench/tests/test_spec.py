"""BENCHMARK.json keeps to its contract, and every name in it finds its
file: configuration, its architecture module, mix, driver, the cell's own
file and metric reader."""
import json
import re
from pathlib import Path

import pytest

from chipbench.run import reader

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# widths never cut: sizes, head sizes, ranks, expansion factors, experts
# per token (the vocabulary may be sliced)
WIDTH = re.compile(r"^(?!vocab_size$).*(_size|_dim|_rank|_factor|"
                   r"experts_per_tok)$")
E2E = {m["name"]: m for m in SPEC["end_to_end"]}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    assert 1 <= SPEC["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
    conf = json.loads((ROOT / c["file"]).read_text())
    assert conf["name"] == c["name"] and conf["source"] == c["source"]
    assert conf["reduced"] == c["reduced"]
    assert (ROOT / "chipbench" / "archs" / f"{conf['bench_arch']}.py") \
        .is_file()
    for key in c["reduced"]:
        assert not WIDTH.search(key)
        assert conf["published"][key] != conf[key]


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_finds_its_files_and_metrics(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    assert len(w["why"]) <= 200
    assert w["config"] in {c["name"] for c in SPEC["configs"]}
    bench = ROOT / "chipbench"
    mix = json.loads((bench / "mixes" / f"{w['traffic']}.json").read_text())
    assert (bench / "drivers" / f"{mix['kind']}.py").exists()
    own = json.loads((bench / "cells" / f"{w['name']}.json").read_text())
    limits = own["limits"]
    assert limits and all("limit" in v and v["why"]
                          for v in limits.values())
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if w["name"] in m.get("workloads", [w["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    per = [m for m in SPEC["per_layer"]
           if w["name"] in m.get("workloads", [w["name"]])]
    assert per and all(m["moves"] in e2e for m in per)


@pytest.mark.parametrize("m", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_a_reader_with_its_unit(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if m["name"] in E2E:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["moves"] in E2E and m["layer"]
    assert reader(m["name"]).UNIT == m["unit"]


def test_names_unique():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
