"""Without a chip, or without the program beside it, a run fails and
prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "internlm2-1.8b.prefill_heavy", "--seed",
        str(2**31 + 5), "--seconds", "1", "--trace", "0"]


def run_here(root, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(stdout):
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_cpu_only_fails_without_result():
    p = run_here(ROOT)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_fail_without_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".jax_cache",
                                                  ".traces"))
    p = run_here(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)
