#!/usr/bin/env python3
"""A set of runs of one cell, one new process each, in one chip call.

    python3 benchmark/tools/run_set.py --workload <cell> --seeds 1,2,3 \
        --out chiprun_out/<label> [--seconds 51] [--trace 0]

Runs ``benchmark/run.py`` of this checkout once per seed, one after the
other (a chip belongs to one process: this one never touches JAX), keeps
each run's whole stdout as ``<out>/<seed>.jsonl`` and the end of its stderr
as ``<out>/<seed>.err``, and prints one line per run: seed, exit code, wall
seconds, and the result line's ``correct`` and metrics.  What the lines
hold is read afterwards by ``tools/cycle_table.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True,
                        help="comma-separated, one run each, in this order")
    parser.add_argument("--out", required=True, metavar="DIR",
                        help="where the runs' lines are kept")
    parser.add_argument("--seconds", type=float, default=None,
                        help="default: BENCHMARK.json's run_seconds")
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    worst = 0
    for seed in args.seeds.split(","):
        t = time.monotonic()
        proc = subprocess.run(
            [sys.executable, os.path.join("benchmark", "run.py"),
             "--workload", args.workload, "--seed", seed,
             "--seconds", str(seconds), "--trace", str(args.trace)],
            cwd=ROOT, capture_output=True, text=True)
        stem = os.path.join(out, f"{seed}.t{args.trace}")
        with open(stem + ".jsonl", "w", encoding="utf-8") as f:
            f.write(proc.stdout)
        with open(stem + ".err", "w", encoding="utf-8") as f:
            f.write(proc.stderr[-8000:])
        lines = proc.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if proc.returncode == 0 else {}
        except (IndexError, ValueError):
            result = {}
        print(json.dumps({
            "seed": int(seed), "rc": proc.returncode,
            "wall_s": round(time.monotonic() - t, 1),
            "correct": result.get("correct"),
            "metrics": {k: v["value"] for k, v in
                        result.get("metrics", {}).items()}}), flush=True)
        if proc.returncode or not result.get("correct"):
            worst = worst or proc.returncode or 1
            print(proc.stderr[-2000:], file=sys.stderr, flush=True)
    return worst


if __name__ == "__main__":
    sys.exit(main())
