#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, at a cell's own
size: the plain reference put in the program's place and computed one
precision below what the configuration states, judged by the same numbers
and limits as the program.  It must come out as not correct.

    python3 benchmark/tools/control.py --workload <cell> --seeds 1,2,3

Needs no server and no timed window; the benchmark's own runs never run it.
One JSON line per seed.  (The same comparison at a tiny size is
``benchmark/tests/test_reference.py``.)
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

# the control that has to fail, by the traffic's kind: the precision below
# the one the configuration states (training computes in bfloat16, serving
# keeps float32).  The serving one does not fail yet, which is why no serving
# cell stands in BENCHMARK.json: PERF.md, Open questions, first
CONTROL_OF = {"train": "fp8", "train_looped": "fp8", "serve_open": "bfloat16"}


def train_control(cfg, traffic, seed, precision):
    import jax.numpy as jnp
    import numpy as np
    from benchmark.kinds.train import token_stream
    from benchmark.lib import program
    ref = program.reference_for(cfg)
    d, job = ref.dims(cfg), cfg["train"]
    n = job["gradient_accumulation_steps"] * job["batch_size"]
    stream = token_stream(seed, d["vocab"],
                          n * job["block_size"] + 1).astype(np.int32)
    xs = jnp.asarray(stream[:-1].reshape(n, job["block_size"]))
    ys = jnp.asarray(stream[1:].reshape(n, job["block_size"]))
    weights = ref.init_params(cfg, seed)
    kw = dict(heads=d["heads"], rows=job["reference_rows"])
    loss, grad = ref.mean_loss_and_grad(weights, xs, ys, **kw)
    c_loss, c_grad = ref.mean_loss_and_grad(weights, xs, ys,
                                            precision=precision, **kw)
    return {"loss_rel_err": abs(c_loss - loss) / abs(loss),
            "grad_rel_err": ref.tree_rel_error(
                ref.as_gpt2_custom(c_grad, d["depth"]),
                ref.as_gpt2_custom(grad, d["depth"]))}


def serve_control(cfg, traffic, seed, precision, seconds):
    from benchmark.kinds.serve_open import regret_numbers, sample_requests
    from benchmark.lib import program, traffic as traffic_lib
    ref = program.reference_for(cfg)
    d = ref.dims(cfg)
    reqs = [r for r in traffic_lib.schedule(traffic, seed, seconds,
                                            d["vocab"], d["block"])
            if r.counted]
    sample = sample_requests(reqs, seed, cfg["correct"]["sample_requests"])
    weights = ref.init_params(cfg, seed)
    regrets = []
    for r in sample:
        tokens = ref.greedy_continue(weights, r.prompt, r.max_new,
                                     heads=d["heads"], block=d["block"],
                                     precision=precision)
        regrets.append(ref.greedy_regret(weights, r.prompt, tokens,
                                         heads=d["heads"], block=d["block"]))
    return regret_numbers(regrets)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--precision", default=None,
                        help="default: the one below the configuration's")
    args = parser.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (cell,) = [w for w in manifest["workloads"] if w["name"] == args.workload]
    (entry,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    cfg = json.load(open(os.path.join(ROOT, entry["file"])))
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json")))
    import jax
    precision = args.precision or CONTROL_OF[traffic["kind"]]
    limits = cfg["correct"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        if traffic["kind"].startswith("train"):
            got = train_control(cfg, traffic, seed, precision)
        else:
            got = serve_control(cfg, traffic, seed, precision,
                                args.seconds or manifest["run_seconds"])
        judged = {k: {"value": v, "limit": limits[k]}
                  for k, v in got.items() if k in limits}
        print(json.dumps({
            "workload": args.workload, "seed": seed, "control": precision,
            "platform": jax.devices()[0].platform,
            "control_correct": all(j["value"] <= j["limit"]
                                   for j in judged.values()),
            **got, "judged": judged,
            "seconds": time.monotonic() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
