"""End-to-end launcher test: train a few steps, kill, auto-resume (the
fault-tolerance loop of launch/train.py)."""
import os
import subprocess
import sys

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _train(ckpt_dir, steps):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--steps", str(steps), "--seq-len", "32", "--batch", "4",
         "--ckpt-every", "5", "--ckpt-dir", ckpt_dir],
        capture_output=True, text=True, timeout=420, env=env, cwd=_ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_train_launcher_runs_and_resumes(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    out1 = _train(ckpt, steps=7)
    assert "step    5" in out1 or "step 5" in out1.replace("   ", " ")
    assert "done" in out1
    # second invocation must auto-resume from the last checkpoint
    out2 = _train(ckpt, steps=12)
    assert "[resume] from step 7" in out2, out2
    assert "done" in out2


def test_train_no_smoke_resolves_to_published_config():
    from repro.configs import get_config
    from repro.launch import train
    assert train.config_from_args(train.parse_args([])) == \
        get_config("internlm2-1.8b", smoke=True)
    assert train.config_from_args(train.parse_args(["--no-smoke"])) == \
        get_config("internlm2-1.8b")


def test_serve_exit_code_reflects_failed_requests():
    from repro.core.resilience.faults import FaultPlan, FaultSpec, inject
    from repro.launch import serve
    argv = ["--requests", "2", "--max-new", "2", "--prompt-len", "4", "6"]
    assert serve.main(argv) == 0
    # every prefill crashes: both requests fail after their retries
    with inject(FaultPlan([FaultSpec("serve.admit", times=None)])):
        assert serve.main(argv) == 1


def test_serve_profile_writes_a_trace(tmp_path):
    from repro.launch import serve
    argv = ["--requests", "2", "--max-new", "2", "--prompt-len", "4", "6",
            "--no-fastpath", "--profile", str(tmp_path)]
    assert serve.main(argv) == 0
    assert list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
