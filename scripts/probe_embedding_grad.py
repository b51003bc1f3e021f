"""On-chip probe: the ways to sum N cotangent rows into a (V, d) table.

    chiprun -- python scripts/probe_embedding_grad.py [--quick]

Times, standalone and on three id distributions (the benchmark's 64-id
cycle, uniform over the vocabulary, a Zipf(1.0) draw with the frequent ids
first), the two backward paths of ops/modules.py::_gather_rows — XLA's
scatter into an fp32 table and the one-hot scan — and the scatter with ids
sorted beforehand (``indices_are_sorted``), with the sort and the row gather
that needs timed apart.  Each timing is 12 calls chained in one program (each
call's ``g`` takes one element of the previous table, so nothing is hoisted
or merged), the median of 7 runs, per call.  Every variant is compared once
with the fp32 scatter.  The smaller shapes are where the one-hot scan could
still win (a toy vocabulary, a few hundred tokens).

One JSON line per timing on stdout and in chiprun_out/embedding_grad.jsonl.
Instrumentation, not part of the framework; PERF.md §6 (PR 30) has the
readings ``_gather_rows_bwd``'s choice was made from.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from penroz_tpu.ops import modules as M

CHAIN = 12
OUT = os.path.join("chiprun_out", "embedding_grad.jsonl")


def id_sets(V, N, seed=0):
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, V + 1, dtype=np.float64)
    return {
        "cycle64": np.tile(rng.choice(V, min(64, V), replace=False),
                           N // min(64, V) + 1)[:N],
        "uniform": rng.integers(0, V, N),
        "zipf": rng.choice(V, N, p=(1 / ranks) / (1 / ranks).sum()),
    }


def chained(fn):
    """``CHAIN`` dependent calls of ``fn(ids, g) -> table`` as one program."""
    def run(ids, g):
        dw = None
        for _ in range(CHAIN):
            dw = fn(ids, g)
            g = g.at[0, 0].add(dw[0, 0].astype(g.dtype) * 0)
        return dw
    return jax.jit(run)


def per_call_ms(fn, ids, g, runs=7):
    jax.block_until_ready(fn(ids, g))
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(ids, g))
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times) / CHAIN


def variants(V, dtype, parts):
    def sort_with_order(ids):
        return jax.lax.sort((ids, jnp.arange(ids.shape[0], dtype=jnp.int32)),
                            num_keys=1, is_stable=False)

    def sort_only(ids, g):
        s, order = sort_with_order(ids)
        return (s + order)[:, None].astype(jnp.float32)

    def sort_gather(ids, g):
        return jnp.take(g, sort_with_order(ids)[1], axis=0)

    out = {
        "onehot_scan": lambda ids, g: M._onehot_rows_grad(ids, g, V, dtype),
        "scatter": lambda ids, g: M._scatter_rows_grad(ids, g, V, dtype),
        "scatter_sorted": lambda ids, g: jnp.zeros(
            (V, g.shape[1]), jnp.float32).at[jnp.sort(ids)].add(
                sort_gather(ids, g).astype(jnp.float32),
                indices_are_sorted=True).astype(dtype),
    }
    if parts:
        out["part_sort"] = sort_only
        out["part_sort_gather"] = sort_gather
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="the cell's shape only")
    ap.add_argument("--tiny", action="store_true",
                    help="a rehearsal off the chip: toy shapes, timings "
                         "meaningless")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind}),
          flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    # (V, N, d, dtype, id sets, parts)
    cases = [(50304, 12288, 768, jnp.bfloat16, None, True)]
    if not args.quick:
        cases += [
            (50304, 8192, 768, jnp.bfloat16, None, False),
            (151936, 8192, 1024, jnp.bfloat16, None, False),
            (50304, 8192, 768, jnp.float32, ["uniform"], False),
            (27, 96, 10, jnp.float32, ["uniform"], False),    # makemore MLP
            (512, 256, 64, jnp.bfloat16, ["uniform"], False),  # rehearsal
        ] + [(50304, n, 768, jnp.bfloat16, ["uniform"], False)
             for n in (256, 1024, 4096)] \
          + [(v, 12288, 768, jnp.bfloat16, ["uniform"], False)
             for v in (256, 1024, 4096, 16384)]
    if args.tiny:
        cases = [(1024, 1024, 128, jnp.bfloat16, None, True),
                 (27, 96, 10, jnp.float32, ["uniform"], False)]
    with open(OUT, "a") as fh:
        for V, N, d, dtype, sets, parts in cases:
            rng = np.random.default_rng(1)
            g = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32), dtype)
            for set_name, ids_np in id_sets(V, N).items():
                if sets is not None and set_name not in sets:
                    continue
                ids = jnp.asarray(ids_np, jnp.int32)
                want = np.asarray(jnp.zeros((V, d), jnp.float32).at[ids].add(
                    g.astype(jnp.float32)))
                for name, fn in variants(V, dtype, parts).items():
                    row = {"V": V, "N": N, "d": d,
                           "dtype": jnp.dtype(dtype).name, "ids": set_name,
                           "variant": name}
                    try:
                        row["ms"] = round(per_call_ms(chained(fn), ids, g), 4)
                        if not name.startswith("part_"):
                            got = np.asarray(jax.jit(fn)(ids, g), np.float32)
                            row["rel_err"] = float(
                                np.abs(got - want).max()
                                / (np.abs(want).max() + 1e-12))
                    except Exception as e:  # noqa: BLE001 — a probe reports
                        row["error"] = f"{type(e).__name__}: {e}"[:300]
                    line = json.dumps(row)
                    print(line, flush=True)
                    fh.write(line + "\n")
                    fh.flush()


if __name__ == "__main__":
    main()
