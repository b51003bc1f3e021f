"""On-chip probe: the flash kernels in both layouts, with what surrounds them.

    chiprun -- python scripts/probe_flash_layouts.py

Times one attention layer's forward and forward + backward on the fused
``(B, T, (Hq + 2·Hkv)·D)`` projection, bf16, at the benchmark cell's shape
(``cell``: micro-batch 12, 12 heads, T = 1024, D = 64: resident forward,
one-pass backward), at T = 4096 (``t4096``: micro-batch 2; the split
backward, ``penroz_flash_bwd_dq`` / ``_dkv``, with head pairs) and at D = 128
with grouped K/V heads, one head a lane block: 8 query heads on 2 (``gqa128``,
a 0.5 ms layer) and 32 on 8 at T = 2048 (``gqa128_2k``: the split backward
and the sum over each K/V head's group at a size such models run)

- ``module_bhtd``: as ``CausalSelfAttention`` ran it before PR 32 — slice q,
  k, v out of the projection, transpose each to ``(B, H, T, D)``,
  ``flash_attention``, transpose back;
- ``kernels_bhtd``: those kernels alone on ``(B, H, T, D)`` operands;
- ``module_btd``: ``flash_attention_btd`` on the projection, nothing around.

Each timing is 12 calls chained in one program with every output consumed
(the next call's input takes an element of each result, so nothing is
dropped, hoisted or merged: PERF.md §6, PR 26's lesson), the median of 7
runs, per call.  Per shape the layouts are compared once with each other and
with ``causal_attention_reference``, output and gradient, and so is ``btd``
under a VMEM budget that forces the chunked forward (checked, not timed).
One JSON line per reading on stdout and in chiprun_out/flash_layouts.jsonl.
Instrumentation, not part of the framework; PERF.md §6 (PR 32) has the
readings.
"""

import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from penroz_tpu.ops.attention import causal_attention_reference
from penroz_tpu.ops.pallas import flash_attention as FA

CHAIN = 12
SHAPES = {"cell": (12, 12, 12, 1024, 64), "t4096": (2, 12, 12, 4096, 64),
          "gqa128": (4, 8, 2, 1024, 128),
          "gqa128_2k": (2, 32, 8, 2048, 128)}     # (B, Hq, Hkv, T, D)
SMALL_VMEM = 2 ** 20    # a budget under which the plan streams K/V: chunked
OUT = os.path.join("chiprun_out", "flash_layouts.jsonl")


def paths(B, H, Hkv, T, D):
    def heads_first(x):
        return x.reshape(B, T, -1, D).transpose(0, 2, 1, 3)

    def split(qkv):
        q, kv = H * D, Hkv * D
        return qkv[..., :q], qkv[..., q:q + kv], qkv[..., q + kv:]

    def module(attend):
        def fn(qkv):
            out = attend(*(heads_first(x) for x in split(qkv)))
            return out.transpose(0, 2, 1, 3).reshape(B, T, H * D)
        return fn

    def kernels_bhtd(qkv):
        """The (B, H, T, D) kernels alone: the operand is reinterpreted,
        not relaid (wrong attention, the same work)."""
        q, k, v = (x.reshape(B, -1, T, D) for x in split(qkv))
        return FA.flash_attention(q, k, v).reshape(B, T, H * D)

    return {"reference": module(causal_attention_reference),
            "module_bhtd": module(FA.flash_attention),
            "module_btd": lambda qkv: FA.flash_attention_btd(
                qkv, heads=H, kv_heads=Hkv),
            "module_btd_chunked": lambda qkv: FA.flash_attention_btd(
                qkv, heads=H, kv_heads=Hkv, vmem_budget=SMALL_VMEM),
            "kernels_bhtd": kernels_bhtd}


def chained(fn, width: int, backward: bool):
    def loss(qkv, w):
        return (fn(qkv).astype(jnp.float32) * w).sum()

    def run(qkv, w):
        for _ in range(CHAIN):
            if backward:
                g = jax.grad(loss)(qkv, w)
                qkv = qkv + (g * 1e-3).astype(qkv.dtype)
            else:
                out = fn(qkv)
                qkv = qkv.at[..., :width].add(out * 1e-3)
        return qkv

    return jax.jit(run)


def median_ms(fn, *args, runs=7):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times) / CHAIN


def rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def main():
    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    lines = []

    def emit(**row):
        row.update(platform=dev.platform, device_kind=dev.device_kind)
        lines.append(row)
        print(json.dumps(row), flush=True)

    for shape, (B, H, Hkv, T, D) in SHAPES.items():
        qkv = jnp.asarray(rng.normal(size=(B, T, (H + 2 * Hkv) * D)),
                          jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(B, T, H * D)), jnp.float32)
        fns = paths(B, H, Hkv, T, D)
        plan = functools.partial(FA.plan_flash, T, T, D, 2, heads=H,
                                 group=H // Hkv, layout="btd", fused_qkv=True)
        emit(shape=shape, plan=plan().describe(),
             plan_chunked=plan(vmem_budget=SMALL_VMEM).describe())
        outs = {name: jax.jit(fn)(qkv) for name, fn in fns.items()
                if name != "kernels_bhtd"}
        grads = {name: jax.jit(jax.grad(
            lambda x, fn=fns[name]: (fn(x).astype(jnp.float32) * w).sum()))(
                qkv) for name in outs}
        for name, other in (("module_btd", "module_bhtd"),
                            ("module_btd", "reference"),
                            ("module_btd_chunked", "reference"),
                            ("module_bhtd", "reference")):
            emit(shape=shape, check=f"{name}_vs_{other}",
                 out_max_abs=float(jnp.abs(
                     outs[name].astype(jnp.float32)
                     - outs[other].astype(jnp.float32)).max()),
                 out_rel=rel(outs[name], outs[other]),
                 grad_rel=rel(grads[name], grads[other]))
        for name in ("module_bhtd", "module_btd", "kernels_bhtd"):
            fwd = median_ms(chained(fns[name], H * D, False), qkv, w)
            both = median_ms(chained(fns[name], H * D, True), qkv, w)
            emit(shape=shape, path=name, fwd_ms=round(fwd, 4),
                 fwd_bwd_ms=round(both, 4))
    with open(OUT, "w") as f:
        f.writelines(json.dumps(row) + "\n" for row in lines)


if __name__ == "__main__":
    main()
