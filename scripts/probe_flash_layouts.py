"""On-chip probe: the flash kernels in both layouts, with what surrounds them.

    chiprun -- python scripts/probe_flash_layouts.py

Times one attention layer's forward and forward + backward, bf16, on the
fused ``(B, T, (Hq + 2·Hkv)·D)`` projection at the benchmark cell's shape
(``cell``: micro-batch 12, 12 heads, T = 1024, D = 64: resident forward,
one-pass backward within the compiler's default VMEM), at T = 4096 with head
pairs (``t4096``: micro-batch 2) and at D = 128 with grouped K/V heads, one
head a lane block: 8 query heads on 2 (``gqa128``, a 0.5 ms layer) and 32 on
8 at T = 2048 (``gqa128_2k``: the sum over each K/V head's group at a size
such models run); and on three ``(B, T, H·D)`` arrays, as a model with RoPE
hands them over, at the looped cell's attention (``loop4k``: micro-batch 2,
16 heads of 128, T = 4096) and at T = 8192 (``d128_8k``).  From T = 2048 on
the one-pass backward asks the compiler for its VMEM
(``FlashPlan.bwd_vmem_bytes``); ``module_btd_split`` is the same call with
``VMEM_LIMIT`` held to the default budget, which is the two-kernel backward
(``penroz_flash_bwd_dq`` / ``_dkv``) those shapes had until PR 42.

- ``module_bhtd``: as ``CausalSelfAttention`` ran it before PR 32 — slice q,
  k, v out of the projection, transpose each to ``(B, H, T, D)``,
  ``flash_attention``, transpose back;
- ``kernels_bhtd``: those kernels alone on ``(B, H, T, D)`` operands;
- ``module_btd``: ``flash_attention_btd`` on the projection (or the three
  arrays), nothing around.

Each timing is 12 calls chained in one program with every output consumed
(the next call's input takes an element of each result, so nothing is
dropped, hoisted or merged: PERF.md §6, PR 26's lesson), the median of 7
runs, per call; ``bwd_ms`` is the difference of the two.  Per shape the
layouts are compared once with each other and with
``causal_attention_reference`` (where its (B, H, T, T) scores fit the chip),
output and gradient, and so are ``btd`` under a VMEM budget that forces the
chunked forward (checked, not timed) and the two backwards with each other.
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
# (B, Hq, Hkv, T, D, q / k / v as three arrays)
SHAPES = {"cell": (12, 12, 12, 1024, 64, False),
          "t4096": (2, 12, 12, 4096, 64, False),
          "gqa128": (4, 8, 2, 1024, 128, False),
          "gqa128_2k": (2, 32, 8, 2048, 128, False),
          "loop4k": (2, 16, 16, 4096, 128, True),
          "d128_8k": (1, 16, 16, 8192, 128, True)}
REFERENCE_SCORES = 2 ** 29  # B·H·T² the jnp reference may hold in f32
SMALL_VMEM = 2 ** 20    # a budget under which the plan streams K/V: chunked
OUT = os.path.join("chiprun_out", "flash_layouts.jsonl")


def split_limit(fn):
    """``fn`` traced with what a call may ask for held to the default
    budget: the plan of every kernel but the backward is the same (no
    argument sets the limit, so this moves the constant the plan reads)."""
    def held(*xs):
        limit, FA.VMEM_LIMIT = FA.VMEM_LIMIT, FA.VMEM_BUDGET
        try:
            return fn(*xs)
        finally:
            FA.VMEM_LIMIT = limit
    return held


def paths(B, H, Hkv, T, D, three):
    """name → function of the operands: the fused projection, or q, k, v."""
    def heads_first(x):
        return x.reshape(B, T, -1, D).transpose(0, 2, 1, 3)

    def parts(*xs):
        if three:
            return xs
        q, kv = H * D, Hkv * D
        return xs[0][..., :q], xs[0][..., q:q + kv], xs[0][..., q + kv:]

    def module(attend):
        def fn(*xs):
            out = attend(*(heads_first(x) for x in parts(*xs)))
            return out.transpose(0, 2, 1, 3).reshape(B, T, H * D)
        return fn

    def kernels_bhtd(*xs):
        """The (B, H, T, D) kernels alone: the operand is reinterpreted,
        not relaid (wrong attention, the same work)."""
        q, k, v = (x.reshape(B, -1, T, D) for x in parts(*xs))
        return FA.flash_attention(q, k, v).reshape(B, T, H * D)

    def btd(**kwargs):
        return lambda *xs: FA.flash_attention_btd(*xs, heads=H, kv_heads=Hkv,
                                                  **kwargs)

    return {"reference": module(causal_attention_reference),
            "module_bhtd": module(FA.flash_attention),
            "module_btd": btd(),
            "module_btd_split": split_limit(btd()),
            "module_btd_chunked": btd(vmem_budget=SMALL_VMEM),
            "kernels_bhtd": kernels_bhtd}


def chained(fn, width: int, backward: bool):
    def loss(xs, w):
        return (fn(*xs).astype(jnp.float32) * w).sum()

    def run(xs, w):
        for _ in range(CHAIN):
            if backward:
                xs = tuple(x + (g * 1e-3).astype(x.dtype)
                           for x, g in zip(xs, jax.grad(loss)(xs, w)))
            else:
                out = fn(*xs)
                xs = (xs[0].at[..., :width].add(out * 1e-3), *xs[1:])
        return xs

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

    for shape, (B, H, Hkv, T, D, three) in SHAPES.items():
        widths = ([H * D, Hkv * D, Hkv * D] if three
                  else [(H + 2 * Hkv) * D])
        xs = tuple(jnp.asarray(rng.normal(size=(B, T, width)), jnp.bfloat16)
                   for width in widths)
        w = jnp.asarray(rng.normal(size=(B, T, H * D)), jnp.float32)
        fns = paths(B, H, Hkv, T, D, three)
        plan = functools.partial(FA.plan_flash, T, T, D, 2, heads=H,
                                 group=H // Hkv, layout="btd",
                                 fused_qkv=not three)
        emit(shape=shape, plan=plan().describe(),
             plan_split=split_limit(plan)().describe(),
             plan_chunked=plan(vmem_budget=SMALL_VMEM).describe())
        if plan().bwd_vmem_bytes <= FA.VMEM_BUDGET:
            del fns["module_btd_split"]     # the same call as module_btd
        if B * H * T * T > REFERENCE_SCORES:
            del fns["reference"]
        checked = [name for name in fns if name != "kernels_bhtd"]
        outs = {name: jax.jit(fns[name])(*xs) for name in checked}
        grads = {name: jnp.concatenate(jax.jit(jax.grad(
            lambda xs, fn=fns[name]: (fn(*xs).astype(jnp.float32)
                                      * w).sum()))(xs), axis=-1)
                 for name in checked}
        for name, other in (("module_btd", "module_bhtd"),
                            ("module_btd", "module_btd_split"),
                            ("module_btd", "reference"),
                            ("module_btd_split", "reference"),
                            ("module_btd_chunked", "reference"),
                            ("module_bhtd", "reference")):
            if name in outs and other in outs:
                emit(shape=shape, check=f"{name}_vs_{other}",
                     out_max_abs=float(jnp.abs(
                         outs[name].astype(jnp.float32)
                         - outs[other].astype(jnp.float32)).max()),
                     out_rel=rel(outs[name], outs[other]),
                     grad_rel=rel(grads[name], grads[other]))
        del outs, grads
        for name in ("module_bhtd", "module_btd", "module_btd_split",
                     "kernels_bhtd"):
            if name not in fns:
                continue
            fwd = median_ms(chained(fns[name], H * D, False), xs, w)
            both = median_ms(chained(fns[name], H * D, True), xs, w)
            emit(shape=shape, path=name, fwd_ms=round(fwd, 4),
                 fwd_bwd_ms=round(both, 4), bwd_ms=round(both - fwd, 4))
    with open(OUT, "w") as f:
        f.writelines(json.dumps(row) + "\n" for row in lines)


if __name__ == "__main__":
    main()
