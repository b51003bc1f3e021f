#!/usr/bin/env python3
"""Where a training window's time went, run by run, from kept lines.

    python3 benchmark/tools/cycle_table.py chiprun_out/<label> [...]

Reads what ``tools/run_set.py`` kept (a run's stdout, one JSON object a
line) and recomputes, from the ``timeline`` line alone, every whole cycle's
parts (``lib/cycles.py::anatomy``) and the rates they give: the rate between
saves that is ``train_tokens_per_s``, the rate of the median back-to-back
step (``train_step_ms``'s), the whole-cycle rate, ``save_edge_ms``; and
``setup_s`` split into its phases.  Then each directory's spread
(interquartile range over median, ``statistics.quantiles(n=4)``; beside it
the spread without the run farthest from the median) and median.  Needs no
chip and no JAX.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from statistics import median, quantiles

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.lib import cycles  # noqa: E402


def phases(path: str) -> dict:
    out = {}
    with open(path, encoding="utf-8") as f:
        for line in f:
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if not isinstance(obj, dict):
                continue
            if "phase" in obj:
                out.setdefault(obj["phase"], obj)
                if obj["phase"] == "window" and "tokens" in obj:
                    out["window"] = obj
            elif "metrics" in obj:
                out["result"] = obj
    return out


def run_row(path: str) -> dict | None:
    ph = phases(path)
    if "timeline" not in ph or "window" not in ph:
        return None
    line, win = ph["timeline"], ph["window"]
    ends = line["epoch_ends"]
    saves = [(a, b) for a, b, periodic in line["saves"] if periodic]
    # the close is the end of a save; the line's times are rounded
    t1 = min((b for _, b in saves), key=lambda b: abs(b - win["seconds"]))
    parts = cycles.anatomy(ends, saves, 0.0, t1)
    per_step = win["tokens"] // win["epochs_in_window"]
    step = median(cycles.steady_steps(ends, saves, 0.0, t1))
    stall = cycles.stall_seconds(saves, 0.0, t1)
    first = [t for t in ends if t > line["saves"][0][1]][:1]
    return {
        "seed": os.path.basename(path).split(".")[0],
        "correct": ph.get("result", {}).get("correct"),
        "cycles": [{"steps": len(c.steps),
                    "steady_median_ms": 1e3 * median(c.steps[1:]),
                    "steady_max_ms": 1e3 * max(c.steps[1:]),
                    "first_after_save_ms": 1e3 * c.steps[0],
                    "second_after_save_ms": 1e3 * c.steps[1],
                    "before_save_ms": 1e3 * c.before_save,
                    "save_s": c.stall,
                    "edge_ms": 1e3 * cycles.save_edge(c, step)}
                   for c in parts],
        "between_saves": win["tokens"] / (t1 - stall),
        "median_rate": per_step / step,
        "whole_cycle": win["tokens"] / t1,
        "save_edge_ms": 1e3 * median(cycles.save_edge(c, step)
                                     for c in parts),
        "setup_s": ph["warm"]["setup_s"],
        "create_model_s": ph["create_model"]["seconds"],
        "first_epoch_s": ph["warm"]["first_epoch_s"],
        # process start to the end of the job's first epoch, and from there
        # (the first cycle's steps and its save) to the opening
        "to_first_epoch_s": (line["opened_at_s"] + first[0]
                             if first else None),
    }


def spread(values: list[float]) -> float:
    q = quantiles(values, n=4)
    return (q[2] - q[0]) / median(values)


def trimmed_spread(values: list[float]) -> float:
    m = median(values)
    kept = sorted(values, key=lambda v: abs(v - m))[:-1]
    return spread(kept)


def main(argv) -> int:
    for directory in argv:
        rows = [r for r in map(run_row, sorted(
            glob.glob(os.path.join(directory, "*.t0.jsonl")))) if r]
        print(f"== {directory}: {len(rows)} run(s)")
        for r in rows:
            print(json.dumps({k: (round(v, 2) if isinstance(v, float) else v)
                              for k, v in r.items() if k != "cycles"}))
            for c in r["cycles"]:
                print("    ", json.dumps({
                    k: (round(v, 2) if isinstance(v, float) else v)
                    for k, v in c.items()}))
        for key in ("between_saves", "median_rate", "whole_cycle",
                    "save_edge_ms", "setup_s"):
            values = [r[key] for r in rows]
            if len(values) >= 4:
                print(f"   {key}: median {median(values):.2f} spread "
                      f"{100 * spread(values):.3f} % (without the farthest "
                      f"{100 * trimmed_spread(values):.3f} %) "
                      f"least {min(values):.2f} most {max(values):.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
