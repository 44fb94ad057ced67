"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout root:
the cell's configuration (``chipbench/configs/<config>.json``), its
traffic mix (``chipbench/mixes/<traffic>.json``, whose ``kind`` names the
driver ``chipbench/drivers/<kind>.py``), the cell's own file
(``chipbench/cells/<cell>.json``: its clients and its output limits),
the architecture module the configuration names under ``bench_arch``
(``chipbench/archs/<bench_arch>.py``: the program's model, the weights,
the reference and the work counts) and one reader per metric
(``chipbench/metrics/<metric>.py``).  With ``--trace 0`` the line holds
the cell's end-to-end metrics; with ``--trace 1`` the profiler runs over
the first part of the window and the line holds its per-layer metrics,
the device's busy time and a breakdown.

Exits non-zero, printing no result, without a TPU or with fewer chips
than the cell asks for.  The last lines on standard error, and the
``checks`` key that ends the result line, give each number compared with
the reference beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    pass


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: dict, workload: str, root: Path = ROOT):
    """The cell named ``workload``: its entry, configuration, mix and own
    file."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    conf = json.loads((root / confs[cell["config"]]["file"]).read_text())
    bench = root / BENCH.name
    mix = json.loads((bench / "mixes" / f"{cell['traffic']}.json")
                     .read_text())
    own = json.loads((bench / "cells" / f"{workload}.json").read_text())
    return cell, conf, mix, own


def load_arch(conf: dict, root: Path = ROOT):
    """The architecture module the configuration names: everything about
    its model that depends on the layers."""
    if "bench_arch" not in conf:
        raise KeyError(f"configuration {conf.get('name')!r} has no "
                       "'bench_arch' key naming its module under "
                       f"{BENCH.name}/archs/")
    return load("archs", conf["bench_arch"], root)


def cell_metrics(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in a run of this kind."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def load(kind: str, name: str, root: Path = ROOT):
    """The module ``chipbench/<kind>/<name>.py`` of the checkout at
    ``root``: a metric's reader, the driver of a mix's kind, or a
    configuration's architecture."""
    path = root / BENCH.name / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT):
    return load("metrics", name, root)


def judge(checks: dict, limits: dict) -> tuple[bool, dict]:
    """Each number compared beside its limit, and whether all keep it."""
    out = {name: {"value": float(checks[name]),
                  "limit": float(lim["limit"])}
           for name, lim in limits.items()}
    return all(c["value"] <= c["limit"] for c in out.values()), out


def set_compile_cache():
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, and otherwise at a fixed path inside the checkout, so that only
    a checkout's first run of a cell compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(BENCH / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs


def device_info(devs, record) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": record["memory_peak_bytes"]}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, devices=None, fault=None,
             controls=()) -> dict:
    """One run of ``workload``: the result line as a dict.  ``devices``
    and ``fault`` are for tests, which run without a chip.  ``controls``
    (``calibrate.py`` and tests only) also judges each named control on
    the same sample by the same limits, under the key ``controls``."""
    spec = load_spec(root)
    cell, conf, mix, own = resolve(spec, workload, root)
    devs = check_devices(cell["chips"]) if devices is None else devices
    from chipbench.lib import work
    from chipbench.lib.context import RunContext
    peak = work.peaks(devs[0].device_kind)
    trace_dir = root / BENCH.name / ".traces" / f"{workload}.{seed}" \
        if trace else None
    if trace_dir is not None and trace_dir.exists():
        shutil.rmtree(trace_dir)
    driver = load("drivers", mix["kind"], root)
    record = driver.run(conf, mix, own, seed, seconds, load_arch(conf, root),
                        trace_dir=trace_dir, fault=fault, t_start=T_START,
                        controls=controls)

    from chipbench.lib import trace as tr
    ctx = RunContext(record, peak,
                     tr.find_xplane(trace_dir) if trace else None)
    metrics = {}
    for m in cell_metrics(spec, workload, trace):
        value = reader(m["name"], root).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    kept, checks = judge(record["checks"], own["limits"])
    correct = record["checked"] > 0 and kept
    out = {"correct": correct, "attempted": record["stats"].attempted,
           "failed": record["stats"].failed, "metrics": metrics,
           "device": device_info(devs, record)}
    if trace:
        t = ctx.trace
        out["device"]["busy_s"] = sum(
            tr.busy_ns(d, t.t0, t.t1) for d in t.devices) / \
            max(1, len(t.devices)) / 1e9
        out["device"]["window_s"] = (t.t1 - t.t0) / 1e9
        out["breakdown"] = {
            "device_ops": tr.top_ops(t.devices[0], t.t0, t.t1)
            if t.devices else [],
            "idle_gaps": tr.named_gaps(t, t.devices[0])
            if t.devices else []}
        shutil.rmtree(trace_dir, ignore_errors=True)
    if controls:
        out["controls"] = {}
        for q in controls:
            kept, c = judge(record["controls"][q], own["limits"])
            out["controls"][q] = {"correct": kept, "checks": c}
        out["gap_stats"] = record["gap_stats"]
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    set_compile_cache()
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(f"correct = {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} "
              f"{verdict}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
