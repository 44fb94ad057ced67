"""A checkout root holding one tiny serving cell, for runs on the CPU."""
from __future__ import annotations

import json
import shutil
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CELL = "tiny.short"

TINY_CONF = {
    "name": "tiny", "source": "the program's internlm2-1.8b smoke preset",
    "program_arch": "internlm2-1.8b", "program_preset": "smoke",
    "bench_arch": "dense",
    "num_hidden_layers": 2, "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
    "initializer_range": 0.02, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "kv_cache_dtype": "int8", "reduced": []}

TINY_MIX = {
    "kind": "serve_closed_loop",
    "prompt": {"ladder": [32, 64], "median": 40, "sigma": 0.5},
    "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
    "block": 10, "queue_per_s": 400, "trace_seconds": 1,
    "check_tokens": 150}

TINY_OWN = {"clients": 4, "limits": {"token_gap": {"limit": 0.5}}}


@dataclass
class FakeDevice:
    """Stands in for a chip in runs that skip the harness's look for
    one."""
    platform: str = "cpu"
    device_kind: str = "TPU v5 lite"


def make_root(tmp: Path, limit: float = 0.5) -> Path:
    """A checkout root whose BENCHMARK.json names only the tiny cell,
    with the real drivers, architecture modules and metric readers."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": TINY_CONF["source"],
                        "file": "chipbench/configs/tiny.json",
                        "reduced": [], "why": "tests"}]
    spec["workloads"] = [{"name": CELL, "config": "tiny",
                          "traffic": "short", "chips": 1, "why": "tests"}]
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "workloads" in m:
                m["workloads"] = [CELL]
    bench = tmp / "chipbench"
    for sub in ("configs", "mixes", "cells"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "drivers", "archs"):
        shutil.copytree(BENCH / sub, bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"),
                        dirs_exist_ok=True)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY_CONF))
    (bench / "mixes" / "short.json").write_text(json.dumps(TINY_MIX))
    own = dict(TINY_OWN, limits={"token_gap": {"limit": limit}})
    (bench / "cells" / f"{CELL}.json").write_text(json.dumps(own))
    return tmp
