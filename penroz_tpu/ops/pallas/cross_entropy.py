"""Pallas TPU fused softmax cross-entropy over large vocabularies.

The CE loss over a (N, V≈50k) logits matrix is pure HBM-bandwidth work, but
both the naive fp32 upcast and a host-level chunked scan leave 4-10× on the
table (measured: optax fp32 ≈ 14.7 ms fwd+bwd, jnp chunk-scan ≈ 29 ms at
N=8192, V=50304 on v5e — against ~2.5 GB of traffic ≈ 3 ms at bandwidth).

Two kernels, mirroring the flash-attention structure
(ops/pallas/flash_attention.py):

- forward — grid (rows, vocab-chunks), vocab innermost and ``arbitrary``:
  streams vocab chunks through VMEM carrying running (max, sumexp) statistics
  plus the label logit picked up in whichever chunk contains it; emits
  per-row ``lse`` and label logit.  The bf16 logits are read exactly once
  and no fp32 copy ever reaches HBM.
- backward — fully parallel grid: ``(softmax - onehot) · scale`` per chunk
  from the forward's saved ``lse``, written directly in the logits dtype;
  ``scale`` is one number a row (the cotangent of that row's loss: 1/N
  for a mean, a token's exit probability over N for the exit loss).

The public entries are :func:`fused_cross_entropy_rows` and
:func:`fused_cross_entropy_mean` in ops/losses.py, which dispatch here on
TPU and to the jnp chunk-scan elsewhere (the jnp path is the correctness
oracle in tests/test_losses.py).  The two calls are named
(``penroz_ce_fwd`` / ``penroz_ce_bwd``) so that a trace and a reader find
them by name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_V = 2048
_NEG_INF = -1e30
_LANES = 128


def _col_ids(vj, block_n: int, block_v: int):
    return vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)


def _fwd_kernel(x_ref, t_ref, lse_ref, ll_ref, m_scr, l_scr, ll_scr, *,
                block_n: int, block_v: int, num_v: int, vocab: int):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        ll_scr[...] = jnp.zeros_like(ll_scr)

    x = x_ref[...].astype(jnp.float32)
    cols = _col_ids(vj, block_n, block_v)
    x = jnp.where(cols < vocab, x, _NEG_INF)  # tail-chunk vocab mask

    m_prev = m_scr[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[:, 0] * alpha + jnp.sum(jnp.exp(x - m_new[:, None]), axis=-1)
    m_scr[...] = jax.lax.broadcast_in_dim(m_new, m_scr.shape, (0,))
    l_scr[...] = jax.lax.broadcast_in_dim(l_new, l_scr.shape, (0,))

    # label logit if this chunk owns it (one hit across the whole vocab loop)
    t = t_ref[:, 0]
    hit = cols == t[:, None]
    ll_scr[...] += jax.lax.broadcast_in_dim(
        jnp.sum(jnp.where(hit, x, 0.0), axis=-1), ll_scr.shape, (0,))

    @pl.when(vj == num_v - 1)
    def _finish():
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        lse_ref[...] = (m_scr[:, 0] + jnp.log(l_safe))[:, None]
        ll_ref[...] = ll_scr[:, 0:1]


def _bwd_kernel(x_ref, t_ref, lse_ref, scale_ref, dx_ref, *, block_n: int,
                block_v: int, vocab: int):
    vj = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    cols = _col_ids(vj, block_n, block_v)
    t = t_ref[:, 0]
    p = jnp.exp(x - lse_ref[...])  # (block_n, block_v); lse broadcasts
    onehot = cols == t[:, None]
    valid = (t >= 0)[:, None]  # padded rows contribute zero gradient
    g = jnp.where(valid & (cols < vocab),
                  (p - onehot) * scale_ref[...], 0.0)  # (block_n, 1) scale
    dx_ref[...] = g.astype(dx_ref.dtype)


def _pad_rows(x2d, t1d, block_n: int):
    from penroz_tpu.ops.losses import pad_rows
    x2d, t1d, _ = pad_rows(x2d, t1d, block_n)
    return x2d, t1d


def ce_forward(logits2d, targets1d, block_n: int = DEFAULT_BLOCK_N,
               block_v: int = DEFAULT_BLOCK_V, interpret: bool = False):
    """Per-row (lse, label_logit), fp32, shapes (N, 1) each (padded rows
    included — callers mask on ``targets < 0``)."""
    x, t = _pad_rows(logits2d, targets1d, block_n)
    n, v = x.shape
    block_v = min(block_v, v)
    num_v = -(-v // block_v)
    grid = (n // block_n, num_v)
    kernel = functools.partial(_fwd_kernel, block_n=block_n, block_v=block_v,
                               num_v=num_v, vocab=v)
    lse, ll = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, _LANES), jnp.float32),
            pltpu.VMEM((block_n, _LANES), jnp.float32),
            pltpu.VMEM((block_n, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * n * v),
            bytes_accessed=int(x.size * x.dtype.itemsize),
            transcendentals=int(n * v)),
        interpret=interpret,
        name="penroz_ce_fwd",
    )(x, t[:, None])
    real_n = logits2d.shape[0]
    return lse[:real_n], ll[:real_n]


def ce_backward(logits2d, targets1d, lse, scale,
                block_n: int = DEFAULT_BLOCK_N,
                block_v: int = DEFAULT_BLOCK_V, interpret: bool = False):
    """``(softmax - onehot) * scale`` in the logits dtype; (N, V).
    ``scale``: fp32 ``(N, 1)``, one cotangent a row (a scalar serves every
    row)."""
    x, t = _pad_rows(logits2d, targets1d, block_n)
    n, v = x.shape
    pad = n - logits2d.shape[0]
    scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32).reshape(-1, 1),
                             (logits2d.shape[0], 1))
    if pad:
        lse = jnp.pad(lse, ((0, pad), (0, 0)))
        scale = jnp.pad(scale, ((0, pad), (0, 0)))
    block_v = min(block_v, v)
    num_v = -(-v // block_v)
    grid = (n // block_n, num_v)
    kernel = functools.partial(_bwd_kernel, block_n=block_n, block_v=block_v,
                               vocab=v)
    dx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_n, block_v), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, v), logits2d.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * n * v),
            bytes_accessed=int(2 * x.size * x.dtype.itemsize),
            transcendentals=int(n * v)),
        interpret=interpret,
        name="penroz_ce_bwd",
    )(x, t[:, None], lse, scale)
    return dx[: logits2d.shape[0]]
