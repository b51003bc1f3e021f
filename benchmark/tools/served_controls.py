#!/usr/bin/env python3
"""The program's reading and the controls' on the same served sequences, in
one process: one run of a serving cell (``run.py``'s own ``main``, nothing
of it changed), and where that run scores its sample, every request the
window finished is scored too, once as served and once for each lower
precision, teacher-forced: at each position of the same prompt and tokens,
the gap of the token that precision puts first
(``reference.greedy_regret(..., chosen_by=)``).

    python3 benchmark/tools/served_controls.py --workload <cell> --seed <n> \
        --seconds 51 --out chiprun_out/<label> [--tag int8]

Needs the chip (or ``--rehearse``).  Keeps every scored token's gap in
``<out>/<seed>[.<tag>].json`` so that a statistic that separates the program
from a control can be looked for afterwards; prints one summary line.  To
read the program's own lower-precision cache path as a control, export its
switch (``TURBO_QUANT_KV_CACHE=1``) and give the run a ``--tag``.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

CONTROLS = ("float32_default", "bfloat16", "fp8")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--tag", default="")
    parser.add_argument("--controls", default=",".join(CONTROLS))
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    controls = [c for c in args.controls.split(",") if c]

    import numpy as np
    from benchmark import run as bench_run
    from benchmark.kinds import serve_open
    from benchmark.lib import program
    judge = serve_open.compare_with_reference

    def judge_then_score_all(ctx, win, d):
        verdict = judge(ctx, win, d)
        t = time.monotonic()
        ref = program.reference_for(ctx["cfg"])
        finished = [r for r in win["requests"]
                    if r.ok and r.token_at[-1] >= win["t0"]]
        weights = ref.init_params(ctx["cfg"], args.seed)
        kw = dict(heads=d["heads"], block=d["block"])
        gaps = {who: [ref.greedy_regret(weights, r.prompt, r.tokens,
                                        chosen_by=by, **kw).tolist()
                      for r in finished]
                for who, by in [("served", None)] + [(c, c) for c in controls]}
        del weights
        summary = {who: serve_open.regret_numbers(
            [np.asarray(g) for g in per_request])
            for who, per_request in gaps.items()}
        out = os.path.join(ROOT, args.out)
        os.makedirs(out, exist_ok=True)
        name = f"{args.seed}{'.' + args.tag if args.tag else ''}.json"
        with open(os.path.join(out, name), "w", encoding="utf-8") as f:
            json.dump({"seed": args.seed, "tag": args.tag,
                       "in_window": [win["t0"] <= r.token_at[-1] < win["t1"]
                                     for r in finished],
                       "prompt_tokens": [len(r.prompt) for r in finished],
                       "verdict": verdict, "summary": summary,
                       "gaps": gaps}, f)
        ctx["say"](phase="served_controls", seed=args.seed, tag=args.tag,
                   requests=len(finished), seconds=time.monotonic() - t,
                   **summary)
        return verdict

    serve_open.compare_with_reference = judge_then_score_all
    return bench_run.main(
        ["--workload", args.workload, "--seed", str(args.seed), "--seconds",
         str(args.seconds), "--trace", "0"]
        + (["--rehearse"] if args.rehearse else []))


if __name__ == "__main__":
    sys.exit(main())
