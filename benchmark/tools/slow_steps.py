#!/usr/bin/env python3
"""Which optimizer steps of a training job were slow, and what the host
thread did in them, from the job's own trace.

    python3 benchmark/tools/slow_steps.py --workload <cell> --seed <n> \
        [--seconds 51] [--trace 0] [--out chiprun_out/<label>]
    python3 benchmark/tools/slow_steps.py --body <saved GET /trace/{id}>.json

The first form runs one cell by calling ``benchmark/run.py``'s ``main`` in
this process (on the chip: the run's own lines come first, the result line
among them), then reads the job trace that the run left in the program's
registry (``lib/program_spans.py::find_trace``: it outlives the model's
deletion and the server's stop); ``--out`` keeps that trace as
``<out>/<seed>.trace.json``, the body ``GET /trace/{id}`` would give.  The
second form takes such a body.  Needs no JAX of its own.

A *step* runs from the end of the step before it (the end of the save, for
a cycle's first) to the end of its ``penroz/train_epoch``:

    gap_before  load_batch  gap_between  train_epoch
    (progress   the loader  (placement)  dispatch + wait
     row, log)

For every cycle after the first periodic save, one ``cycle`` line (its
steps, their median, its closing save's host passes with their accounts)
and one ``slow_step`` line for each step longer than 1.05 x the median of
all such steps: its parts, the ``host`` accounts of its ``load_batch`` and
``train_epoch`` (``utils/tracing.py``: cpu_ms, sys_ms, faults, switches;
``waited_ms`` = duration - cpu_ms), the loader's ``scan_ms`` / ``gather_ms``,
and every ``penroz/ckpt_flush`` or ``penroz/ckpt_save`` that overlapped it
on the clock, with the overlap.  The run's ``window`` line says how many of
the cycles the window held (the first ``cycles`` of them); on a ``--trace
1`` run the later ones ran beside the profiler and the trace's reduction,
which write and read files of their own: read those apart.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from statistics import median

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

SLOW = 1.05
SAVE, FLUSH = "penroz/ckpt_save", "penroz/ckpt_flush"
LOAD, EPOCH = "penroz/load_batch", "penroz/train_epoch"


def walk(node):
    yield node
    for child in node.get("children", []):
        yield from walk(child)


def account(span: dict) -> dict:
    """A span's duration with its ``host`` account and what it waited."""
    out = {"ms": span["duration_ms"], **span.get("host", {})}
    if "host" in span and span["duration_ms"] is not None:
        out["waited_ms"] = round(
            span["duration_ms"] - span["host"]["cpu_ms"], 3)
    return out


def save_passes(save: dict) -> dict:
    """A save's children by short name, each with its account."""
    return {"tag": save.get("meta", {}).get("tag"),
            "bytes": save.get("meta", {}).get("bytes"),
            "ms": save["duration_ms"],
            **{c["name"].split("/ckpt_")[-1]: account(c)
               for c in save.get("children", [])}}


def steps_of(top: list[dict], t0: float, t1: float) -> list[dict]:
    """The steps whose epoch ended in ``(t0, t1]``, part by part; ``top``
    are the job's top-level spans in the order they opened."""
    out, start, batch = [], t0, None
    for sp in top:
        if sp["t1_ms"] is None or sp["t0_ms"] < t0 or sp["t1_ms"] > t1:
            continue
        if sp["name"] == LOAD:
            batch = sp
        elif sp["name"] == EPOCH and batch is not None:
            out.append({
                "epoch": sp.get("meta", {}).get("epoch"),
                "t0_ms": start, "t1_ms": sp["t1_ms"],
                "step_ms": round(sp["t1_ms"] - start, 3),
                "gap_before_ms": round(batch["t0_ms"] - start, 3),
                "load_batch": {
                    **account(batch),
                    "scan_ms": batch.get("meta", {}).get("scan_ms"),
                    "gather_ms": batch.get("meta", {}).get("gather_ms")},
                "gap_between_ms": round(sp["t0_ms"] - batch["t1_ms"], 3),
                "train_epoch": {
                    **account(sp),
                    **{c["name"].split("/")[-1] + "_ms": c["duration_ms"]
                       for c in sp.get("children", [])
                       if c["name"].startswith("penroz/train_")}},
            })
            start, batch = sp["t1_ms"], None
    return out


def overlaps(step: dict, others: list[dict]) -> list[dict]:
    """Saves and flushes that ran while ``step`` did (an open one runs on
    to the end of what was recorded)."""
    out = []
    for sp in others:
        end = sp["t1_ms"] if sp["t1_ms"] is not None else float("inf")
        shared = min(end, step["t1_ms"]) - max(sp["t0_ms"], step["t0_ms"])
        if shared > 0:
            out.append({"name": sp["name"],
                        "tag": sp.get("meta", {}).get("tag"),
                        "overlap_ms": round(shared, 3), **account(sp)})
    return out


def report(body: dict) -> list[dict]:
    """The lines for one ``GET /trace/{id}`` body of a ``/train/`` job."""
    top = body["root"].get("children", [])
    saves = [sp for sp in top if sp["name"] == SAVE
             and sp.get("meta", {}).get("periodic")
             and sp["t1_ms"] is not None]
    background = [sp for sp in walk(body["root"])
                  if sp["name"] in (SAVE, FLUSH)]
    cycles = [(a["t1_ms"], b["t1_ms"], b) for a, b in zip(saves, saves[1:])]
    per_cycle = [steps_of(top, t0, t1) for t0, t1, _ in cycles]
    every = [s["step_ms"] for steps in per_cycle for s in steps]
    if not every:
        return [{"phase": "slow_steps", "note": "no whole cycle after the "
                 "first periodic save in this trace",
                 "periodic_saves": len(saves)}]
    typical = median(every)
    lines = [{"phase": "slow_steps", "request_id": body.get("request_id"),
              "cycles": len(cycles), "steps": len(every),
              "median_step_ms": round(typical, 3),
              "slow_over_ms": round(SLOW * typical, 3),
              "dropped_spans": body.get("dropped_spans"),
              "first_save": save_passes(saves[0])}]
    for n, ((t0, t1, save), steps) in enumerate(zip(cycles, per_cycle), 1):
        lines.append({
            "phase": "cycle", "cycle": n, "steps": len(steps),
            "step_ms": [s["step_ms"] for s in steps],
            "load_batch_ms": [s["load_batch"]["ms"] for s in steps],
            "edge_ms": round(sum(s["step_ms"] - typical for s in steps), 3),
            "save": save_passes(save)})
        for k, s in enumerate(steps, 1):
            if s["step_ms"] > SLOW * typical:
                lines.append({"phase": "slow_step", "cycle": n,
                              "step_in_cycle": k,
                              "over_median_ms": round(
                                  s["step_ms"] - typical, 3),
                              **s, "beside": overlaps(s, background)})
    return lines


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--body", metavar="FILE",
                        help="a saved GET /trace/{id} body; runs nothing")
    parser.add_argument("--workload")
    parser.add_argument("--seed", type=int)
    parser.add_argument("--seconds", type=float, default=None,
                        help="default: BENCHMARK.json's run_seconds")
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--out", metavar="DIR",
                        help="keep the job's trace there")
    parser.add_argument("--rehearse", action="store_true",
                        help="run.py's: tiny sizes on any backend")
    args = parser.parse_args(argv)
    if args.body:
        with open(args.body, encoding="utf-8") as f:
            body = json.load(f)
    else:
        if args.workload is None or args.seed is None:
            parser.error("--workload and --seed, or --body")
        from benchmark import run
        from benchmark.lib import program_spans
        with open(os.path.join(ROOT, "BENCHMARK.json"),
                  encoding="utf-8") as f:
            seconds = args.seconds or json.load(f)["run_seconds"]
        rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(seconds),
                       "--trace", str(args.trace)]
                      + ["--rehearse"] * args.rehearse)
        if rc:
            return rc
        trace = program_spans.find_trace()
        if trace is None:
            print("slow_steps.py: the program left no /train/ job trace "
                  "(PENROZ_TRACE_SAMPLE=0, or a program without one)",
                  file=sys.stderr)
            return 1
        body = trace.to_dict()
        if args.out:
            out = os.path.join(ROOT, args.out)
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, f"{args.seed}.trace.json"), "w",
                      encoding="utf-8") as f:
                json.dump(body, f)
    for line in report(body):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
