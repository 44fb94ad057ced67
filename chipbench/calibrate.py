"""Readings that set a cell's output limits: the program's and the
controls', on many seeds in one process.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--out chiprun_out/calibrate.jsonl]

Each seed is one whole run of the cell (``run.run_cell``: set-up, the
window, the comparison with the reference), which also judges the
controls by the cell's limits: the reference in int8 and in float8, read
at each position of the same prompts and served tokens.  Prints one JSON
line per seed.  The benchmark's own runs never run a control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CONTROLS = ("fp8", "int8")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH.parent)]
    from chipbench import run
    run.set_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = run.run_cell(args.workload, seed, args.seconds, False,
                           controls=CONTROLS)
        line = {"workload": args.workload, "seed": seed,
                "correct": out["correct"], "attempted": out["attempted"],
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "checks": out["checks"], "controls": out["controls"],
                "gap_stats": out["gap_stats"],
                "memory_peak_bytes": out["device"]["memory_peak_bytes"],
                "wall_s": time.perf_counter() - t}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
