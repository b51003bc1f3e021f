"""Pallas TPU chunked gated linear-attention (SSD) scan.

The token-sequential recurrence

    S_t = g_t S_{t-1} + k_t ⊗ v_t,   y_t = q_t · S_t

is pure VPU latency when unrolled per token.  The chunked (state-space
duality) form turns all but one small carry into MXU matmuls: with
``La_t = Σ_{i≤t} log g_i`` (inclusive, per chunk)

    y_t   = e^{La_t} (q_t · S_0) + Σ_{j≤t} e^{La_t − La_j} (q_t · k_j) v_j
    S_end = e^{La_L} S_0 + Σ_j e^{La_L − La_j} k_j ⊗ v_j

Both exponents are ≤ 0 (gates in (0, 1)), so every decay factor is in
(0, 1] — no rescaling pass needed.

Grid ``(B·H, T/block_t)`` with the chunk axis innermost and ``arbitrary``;
the (dk, dv) carry state lives in a VMEM scratch that persists across the
chunk loop (same structure as ops/pallas/cross_entropy.py's running stats).
The jnp twin :func:`gla_chunked_reference` implements the identical chunk
math for the interpret-mode oracle test, and the *sequential* oracle lives
in ops/ssm.py::gla_full_reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_T = 128
_LOG_EPS = 1e-6  # floor before log: sigmoid underflow -> exactly-0 gate
_NEG_INF = -1e30


def _dot(a, b, contract):
    """fp32 matmul at true fp32 precision (the MXU default multiplies f32
    operands in bf16 passes — too coarse for products of decay factors)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)


def _chunk_body(q, k, v, lg, s0):
    """One chunk in fp32: (y, s_end) from (block_t, ·) operands + carry;
    ``lg`` is the (block_t, 1) column of log gates.

    Written in what Mosaic lowers: no cumsum primitive and no transpose,
    so the inclusive prefix sum ``La`` is built in both orientations by
    masked reductions over a (t, t) broadcast — along sublanes for the
    row form, then the diagonal of that for the column form.
    """
    t = lg.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    la_row = jnp.sum(jnp.where(row <= col, lg, 0.0), axis=0,
                     keepdims=True)                        # (1, t)
    la_col = jnp.sum(jnp.where(row == col, la_row, 0.0), axis=1,
                     keepdims=True)                        # (t, 1)
    la_end = jnp.sum(lg, axis=0, keepdims=True)            # (1, 1)
    y = _dot(q * jnp.exp(la_col), s0, ((1,), (0,)))
    scores = _dot(q, k, ((1,), (1,)))
    decay = jnp.exp(jnp.where(row >= col, la_col - la_row, _NEG_INF))
    y = y + _dot(scores * decay, v, ((1,), (0,)))
    kd = k * jnp.exp(la_end - la_col)
    s_end = jnp.exp(la_end) * s0 + _dot(kd, v, ((0,), (0,)))
    return y, s_end


def _gla_kernel(q_ref, k_ref, v_ref, lg_ref, o_ref, s_scr, *, block_t: int):
    tj = pl.program_id(1)

    @pl.when(tj == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lg = lg_ref[0].astype(jnp.float32)
    y, s_end = _chunk_body(q, k, v, lg, s_scr[...])
    s_scr[...] = s_end
    o_ref[0] = y.astype(o_ref.dtype)


def gla_chunked(q, k, v, g, block_t: int = DEFAULT_BLOCK_T,
                interpret: bool = False):
    """Chunked GLA over (B, T, H, ·) inputs; gates g (B, T, H) in (0, 1).

    Returns y (B, T, H, dv) fp32.  The ragged tail is padded with g = 1,
    k = 0 — the pad tokens leave the carry untouched and their outputs are
    sliced off.
    """
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    block_t = min(block_t, max(T, 8))
    pad = -T % block_t
    lg = jnp.log(jnp.maximum(g.astype(jnp.float32), _LOG_EPS))
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        lg = jnp.pad(lg, ((0, 0), (0, pad), (0, 0)))
    Tp = T + pad

    def flat(x):  # (B, Tp, H, d) -> (B*H, Tp, d)
        return x.transpose(0, 2, 1, 3).reshape(B * H, Tp, x.shape[-1])

    qf, kf, vf = flat(q), flat(k), flat(v)
    # Trailing unit dim: a 2-D (B*H, Tp) gate array would need a
    # (1, block_t) block, and Mosaic requires a block's last two dims to be
    # (8, 128)-divisible or the array's own.
    lgf = lg.transpose(0, 2, 1).reshape(B * H, Tp, 1)
    num_t = Tp // block_t
    kernel = functools.partial(_gla_kernel, block_t=block_t)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, num_t),
        in_specs=[
            pl.BlockSpec((1, block_t, dk), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_t, dk), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_t, dv), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_t, 1), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_t, dv), lambda i, j: (i, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B * H, Tp, dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * B * H * Tp * block_t * (dk + dv)),
            bytes_accessed=int(qf.size * 4 + kf.size * 4 + 2 * vf.size * 4),
            transcendentals=int(B * H * Tp * (block_t + 2))),
        interpret=interpret,
    )(qf, kf, vf, lgf)
    return (out.reshape(B, H, Tp, dv).transpose(0, 2, 1, 3))[:, :T]


def gla_chunked_reference(q, k, v, g, block_t: int = DEFAULT_BLOCK_T):
    """jnp twin of the kernel's chunk math (host-side correctness oracle)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    block_t = min(block_t, max(T, 8))
    pad = -T % block_t
    lg = jnp.log(jnp.maximum(g.astype(jnp.float32), _LOG_EPS))
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        lg = jnp.pad(lg, ((0, 0), (0, pad), (0, 0)))
    Tp = T + pad
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tp, dk).astype(jnp.float32)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Tp, dk).astype(jnp.float32)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Tp, dv).astype(jnp.float32)
    lgf = lg.transpose(0, 2, 1).reshape(B * H, Tp)

    def per_seq(qs, ks, vs, lgs):
        def step(s0, xt):
            qc, kc, vc, lgc = xt
            y, s_end = _chunk_body(qc, kc, vc, lgc[:, None], s0)
            return s_end, y
        xs = (qs.reshape(-1, block_t, dk), ks.reshape(-1, block_t, dk),
              vs.reshape(-1, block_t, dv), lgs.reshape(-1, block_t))
        _, ys = jax.lax.scan(step, jnp.zeros((dk, dv), jnp.float32), xs)
        return ys.reshape(Tp, dv)

    out = jax.vmap(per_seq)(qf, kf, vf, lgf)
    return (out.reshape(B, H, Tp, dv).transpose(0, 2, 1, 3))[:, :T]
