"""Pallas TPU ragged paged attention: one dispatch over a mixed batch.

The serving engine's three phase-specialized programs — chunked prefill
(T = chunk), batched decode (T = 1) and speculative verify (T = K+1) —
become ONE kernel over a *packed* query array.  The packed axis is cut
into fixed ``block_q``-token blocks and each block carries a descriptor
``(row, q_pos0, q_valid, kv_len)``: which sequence it belongs to, the
absolute position of its first query token, how many of its ``block_q``
slots are real, and the row's total valid KV length after the current
append.  A decode step is one descriptor with ``q_valid = 1``; a
64-token prefill chunk is ``64 / block_q`` descriptors; a verify row is
``ceil((K+1)/block_q)`` — all side by side in the same grid, which is
what deletes the scheduler's phase distinction (serve/decode_scheduler).

KV is read straight through the paged block table (scalar-prefetched,
one physical page resident in VMEM per grid step, same dataflow as
ops/pallas/paged_attention.py) — no ``row_view`` dense materialization.
Out-of-band pages clamp their index so the DMA is elided, and the
*logical* key positions mask the clamped re-fetch to zero.  ALiBi,
logit softcap, sliding windows, GQA head grouping and int8 (TurboQuant)
per-token dequantization carry over from the decode kernels.

Grid: (descriptor, kv_head, logical_page); the page dimension is
sequential so online-softmax scratch persists across it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from penroz_tpu.ops.pallas.flash_attention import _LANES

_NEG_INF = -1e30

#: Descriptor columns: (row, q_pos0, q_valid, kv_len).  ``row = -1`` marks
#: a padding descriptor (q_valid = 0); its queries mask out entirely and
#: its output block is zero.
DESC_COLS = 4
DEFAULT_BLOCK_Q = 8


def default_block_q() -> int:
    """Packed query tokens per descriptor block
    (``PENROZ_RAGGED_BLOCK_Q``, default 8 — the fp32 sublane tile, so a
    decode step wastes at most 7 padded query rows while a 256-token
    prefill chunk still amortizes to 32 well-shaped MXU blocks)."""
    import os
    raw = os.environ.get("PENROZ_RAGGED_BLOCK_Q", str(DEFAULT_BLOCK_Q))
    try:
        n = int(raw)
    except ValueError:
        return DEFAULT_BLOCK_Q
    return n if n >= 1 else DEFAULT_BLOCK_Q


def _ragged_kernel(desc_ref, table_ref, q_ref, k_ref, v_ref, *rest,
                   page_size: int, grid_pages: int, block_q: int,
                   group: int, sm_scale: float, quantized: bool,
                   window=None, use_alibi: bool = False, softcap=None):
    """One (descriptor, kv_head, page) step: the block's ``group·block_q``
    grouped query rows attend one physical page.

    q_ref: (1, group, block_q, D) — descriptor d's packed queries, row
    r ↦ (g = r // block_q, t = r % block_q).  k_ref/v_ref: (1, page_size,
    D) — the j-th logical page of the descriptor's sequence, fetched
    through the block table by the index map (clamped in-band).  The
    causal bound is *per query token*: key position kp is attended when
    ``kp ≤ q_pos0 + t`` — exactly the sequential per-phase oracle's mask,
    so a mixed batch is bit-identical to running its phases one by one.
    """
    rest = list(rest)
    ks_ref = vs_ref = slopes_ref = None
    if quantized:
        ks_ref, vs_ref = rest[:2]
        rest = rest[2:]
    if use_alibi:
        slopes_ref = rest[0]
        rest = rest[1:]
    o_ref, m_scr, l_scr, acc_scr = rest
    d = pl.program_id(0)
    j = pl.program_id(2)
    gt = group * block_q
    q_pos0 = desc_ref[d * DESC_COLS + 1]
    q_valid = desc_ref[d * DESC_COLS + 2]
    # Keys this block can ever attend: its own last query position + 1
    # (≤ kv_len — later chunks of the same row carry the larger bound).
    need = q_pos0 + q_valid
    live = j * page_size < need
    if window is not None:
        # pages entirely below every query's window contribute nothing
        live &= (j + 1) * page_size - 1 > q_pos0 - window

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live)
    def _block():
        q = q_ref[0].reshape(gt, q_ref.shape[-1])
        k = k_ref[0]
        v = v_ref[0]
        if quantized:
            k = (k.astype(jnp.float32) * ks_ref[0]).astype(q.dtype)
            v = (v.astype(jnp.float32) * vs_ref[0]).astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # Row r is query token t = r % block_q at absolute position
        # q_pos0 + t; rows t ≥ q_valid are packing padding.
        t = jax.lax.broadcasted_iota(jnp.int32, (gt, page_size), 0) % block_q
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (gt, page_size), 1)
        if use_alibi:
            slope = slopes_ref[0][:, 0]
            s = s + slope[:, None] * (
                k_pos - (q_pos0 + t)).astype(jnp.float32)
        mask = (t < q_valid) & (k_pos <= q_pos0 + t)
        if window is not None:
            mask &= k_pos > q_pos0 + t - window
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        # _NEG_INF is finite: padding rows and clamped re-fetches of
        # in-band pages standing in for out-of-band ones are fully
        # masked and would otherwise get p = exp(-1e30 - -1e30) = 1.
        p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(j == grid_pages - 1)
    def _finish():
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = acc_scr[...] / l_safe[:, None]
        o_ref[0] = out.reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def ragged_paged_attention(q, flat_k, flat_v, block_table, page_size: int,
                           descs, k_scale=None, v_scale=None,
                           interpret: bool = False, window=None,
                           alibi=None, scale=None, softcap=None):
    """Unified mixed-batch attention over a paged pool.

    q: (1, Hq, Tp, D) PACKED queries — Tp = num_descs · block_q slots in
    descriptor order, padding slots arbitrary; flat_k/flat_v: (Hkv,
    num_pages · page_size, D) head-major pools; block_table: (B,
    pages_per_seq); descs: (num_descs, 4) int32 ``(row, q_pos0, q_valid,
    kv_len)`` per packed block (row = -1 padding).  With ``k_scale``/
    ``v_scale`` (``(Hkv, rows, 1)`` fp32) the pools are int8 and pages
    dequantize in VMEM.  Output is packed exactly like ``q``; padding
    slots come back zero.  Matches the jnp oracle
    (ops/attention.py::ragged_paged_attention_reference) exactly.
    """
    _, Hq, Tp, D = q.shape
    Hkv = flat_k.shape[0]
    group = Hq // Hkv
    NB = descs.shape[0]
    if NB == 0 or Tp % NB != 0:
        raise ValueError(f"packed length {Tp} must be a positive multiple "
                         f"of the descriptor count {NB}")
    block_q = Tp // NB
    pages_per_seq = block_table.shape[1]
    grid_pages = pages_per_seq
    sm_scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together "
                         "(int8 pools carry scales for both streams)")
    quantized = k_scale is not None

    # (1, Hq, Tp, D) → (Hkv, group, Tp, D): head order is kv-major
    # (matches _group_query_heads), so this is a pure reshape.
    q_rows = q.reshape(Hkv, group, Tp, D)
    descs_flat = jnp.asarray(descs, jnp.int32).reshape(-1)
    # Unassigned pages (-1) only back masked positions; clamp them onto
    # page 0 so the DMA index is in-pool.
    table = jnp.maximum(block_table, 0).astype(jnp.int32).reshape(-1)

    def page_lookup(d, j, desc_ref, table_ref):
        # Clamp out-of-band steps to the nearest in-band logical page:
        # same physical index ⇒ the DMA is elided, so pages past the
        # block's causal bound (and below its window band) are never
        # fetched from HBM.  Padding descriptors (row = -1) clamp to row
        # 0 — their queries are fully masked.
        row = jnp.maximum(desc_ref[d * DESC_COLS], 0)
        need = (desc_ref[d * DESC_COLS + 1]
                + desc_ref[d * DESC_COLS + 2])
        hi = jax.lax.div(need + page_size - 1, page_size)
        j_eff = jnp.minimum(j, jnp.maximum(hi - 1, 0))
        if window is not None:
            lo_pos = jnp.maximum(
                desc_ref[d * DESC_COLS + 1] - int(window) + 1, 0)
            j_eff = jnp.maximum(j_eff, jax.lax.div(lo_pos, page_size))
        return table_ref[row * pages_per_seq + j_eff]

    def page_spec(width):
        return pl.BlockSpec(
            (1, page_size, width),
            lambda d, h, j, desc_ref, table_ref:
                (h, page_lookup(d, j, desc_ref, table_ref), 0),
            memory_space=pltpu.VMEM)

    use_alibi = alibi is not None
    kernel = functools.partial(
        _ragged_kernel, page_size=page_size, grid_pages=grid_pages,
        block_q=block_q, group=group, sm_scale=sm_scale,
        quantized=quantized,
        window=int(window) if window is not None else None,
        use_alibi=use_alibi,
        softcap=float(softcap) if softcap is not None else None)

    in_specs = [
        pl.BlockSpec((1, group, block_q, D),
                     lambda d, h, j, desc_ref, table_ref: (h, 0, d, 0),
                     memory_space=pltpu.VMEM),
        page_spec(D),
        page_spec(D),
    ]
    operands = [q_rows.reshape(Hkv, group, Tp, D), flat_k, flat_v]
    if quantized:
        in_specs += [page_spec(1), page_spec(1)]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
    if use_alibi:
        # (Hkv, group·block_q, 1) per-query-row slopes — row r belongs to
        # query head h·group + r // block_q
        slope_rows = np.repeat(
            np.asarray(alibi, np.float32).reshape(Hkv, group), block_q,
            axis=1)[..., None]
        in_specs += [pl.BlockSpec(
            (1, group * block_q, 1),
            lambda d, h, j, desc_ref, table_ref: (h, 0, 0),
            memory_space=pltpu.VMEM)]
        operands += [jnp.asarray(slope_rows)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(NB, Hkv, grid_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, group, block_q, D),
            lambda d, h, j, desc_ref, table_ref: (h, 0, d, 0),
            memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((group * block_q, _LANES), jnp.float32),
            pltpu.VMEM((group * block_q, _LANES), jnp.float32),
            pltpu.VMEM((group * block_q, D), jnp.float32),
        ],
    )
    span = pages_per_seq * page_size
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, group, Tp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * Hq * Tp * span * D),
            bytes_accessed=int(
                2 * q.size * q.dtype.itemsize
                + NB * (2 * Hkv * span * D * flat_k.dtype.itemsize
                        + (2 * Hkv * span * 4 if quantized else 0))),
            transcendentals=int(Hq * Tp * span)),
        interpret=interpret,
    )(descs_flat, table, *operands)
    return out.reshape(1, Hq, Tp, D)
