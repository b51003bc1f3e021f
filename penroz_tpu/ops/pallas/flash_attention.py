"""Pallas TPU flash-attention (causal, GQA, dropout) — forward + backward.

Blockwise online-softmax attention: the (T, S) score matrix never exists;
each (query tile, key tile) pair is scored, soft-maxed against running
(max, sum) statistics and contracted with V in VMEM.  This is the fusion the
reference gets from ``F.scaled_dot_product_attention``'s cuDNN flash kernels
(reference: neural_net_layers.py:92), built directly on the MXU.

How the score matrix is tiled and walked is a *plan* (:func:`plan_flash`),
a pure function of the shapes and a VMEM budget — no knob:

- **resident** (a head's K and V fit in VMEM, the training shapes): one grid
  step owns ``heads_per_step`` heads × ``q_rows`` query rows; K/V are fetched
  once and ``lax.fori_loop``s walk exactly the key tiles that meet the band
  of each query tile — no grid step and no DMA for a tile above the diagonal
  or left of the window.
- **chunked** (long S): K/V tiles stream through the innermost grid
  dimension as before; a dead step's block index is clamped onto the nearest
  live tile, so it repeats a block already in VMEM (no DMA) and costs only
  the grid step.

Either way only tiles the band's edge crosses build a mask; tiles wholly
inside it skip the iotas, the compare and the select.  The softmax scale is
folded into the (block_q, D) query tile, off the (block_q, block_k) scores.

The backward recomputes probabilities from the forward's saved logsumexp:

- **fused** (a head's operands and its f32 dQ fit in VMEM): one kernel, key
  tiles outermost; each live tile's s, p, dp, ds are computed once (on the
  transposed tile, so logsumexp and δ arrive as lane-dense rows) and feed
  dV += p̃ᵀ·dO, dK += dSᵀ·Q and dQ += dS·K — five matmuls and one ``exp``.
- otherwise the two-kernel split: ``_dq_kernel`` (query tiles resident, K/V
  streaming) and ``_dkv_kernel`` (key tiles resident, Q/dO streaming).

GQA: per-query-head dK/dV, summed over the group outside the kernels.

Dropout runs *inside* the kernels via a counter-based position hash
(lowbias32-style mixer over (q_pos, k_pos, seed)), so the keep-mask needs no
HBM storage, is identical across the forward and the backward kernels by
construction, and — unlike the hardware PRNG — can be reproduced exactly by
the jnp oracle (:func:`dropout_keep_mask_reference`) for equivalence tests.
"""

from __future__ import annotations

import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from penroz_tpu.utils import tracing

log = logging.getLogger(__name__)

_NEG_INF = -1e30
_LANES = 128  # f32 scratch lane width for the (m, l) carries
_HEAD_SEED_PRIME = np.int32(0x632BE5A7)

# What a kernel's blocks, scratch and tile temporaries may take by the
# estimates below: v5e's scoped-VMEM default is 16 MiB, the rest is Mosaic's.
VMEM_BUDGET = 12 * 2 ** 20
# Tiles (block_q, block_k) by direction and score elements a grid step should
# cover (a step costs ~0.35 µs; a head of T = S = 1024, D = 64 takes ~5 µs
# forward): from the sweep on one v5e at (12, 12, 1024, 64) bf16 causal,
# CHANGES.md PR 26.
_FWD_TILE = (512, 512)
_BWD_TILE = (512, 512)
_STEP_SCORES = 2 ** 19


def _dot_precision(dtype):
    """HIGHEST for f32 operands (some backends default f32 dots to bf16-
    class multiplies); default for bf16 — Mosaic rejects fp32 contract
    precision on bf16 operands, and the MXU is bf16-native anyway."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _dot(a, b, contract):
    """f32-accumulated ``a·b`` contracting ``contract = (dim of a, dim of b)``."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_dot_precision(a.dtype))


def _keep_mask(q_pos, k_pos, seed, rate: float):
    """Boolean keep-mask from a position hash (True = keep).

    ``q_pos``/``k_pos``: int32 arrays broadcastable against each other
    (absolute sequence positions); ``seed``: int32 scalar already mixed
    with the (batch, head) index.  Pure jnp — traced identically inside
    the Pallas kernels and in the test oracle, so the mask is exactly
    reproducible.
    """
    x = (q_pos.astype(jnp.uint32) * np.uint32(0x9E3779B1)
         ^ k_pos.astype(jnp.uint32) * np.uint32(0x85EBCA77)
         ^ seed.astype(jnp.uint32) * np.uint32(0xC2B2AE3D))
    x = x ^ (x >> 16)
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * np.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    threshold = np.uint32(min(int((1.0 - rate) * 2.0 ** 32), 2 ** 32 - 1))
    return x < threshold


def dropout_keep_mask_reference(seed, b, h, num_heads: int, T: int, S: int,
                                rate: float):
    """(T, S) keep-mask the kernels generate for batch ``b``, head ``h``."""
    q_pos = jnp.arange(T, dtype=jnp.int32)[:, None]
    k_pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    seed_bh = (jnp.asarray(seed, jnp.int32)
               + jnp.asarray(b * num_heads + h, jnp.int32)
               * _HEAD_SEED_PRIME)
    return _keep_mask(q_pos, k_pos, seed_bh, rate)


def _head_operands(seed_ref, alibi_ref, b, h, num_heads: int,
                   use_alibi: bool, dropout_rate: float):
    """(ALiBi slope, dropout seed) of head ``h`` from SMEM; None where the
    feature is off."""
    slope = alibi_ref[h] if use_alibi else None
    seed = (seed_ref[0] + (b * num_heads + h) * _HEAD_SEED_PRIME
            if dropout_rate > 0.0 else None)
    return slope, seed


def _largest_dividing_block(n: int, preferred: int) -> int:
    """Largest power-of-two block ≤ preferred that divides n (min 128)."""
    block = min(preferred, n)
    while block > 128 and n % block != 0:
        block //= 2
    return block


# ---------------------------------------------------------------------------
# the band: which tiles are live, which of them need a mask
# ---------------------------------------------------------------------------
#
# Written once for Python ints (the plan's tests, the index maps' constants)
# and for the int32 scalars of a kernel or an index map.  Every operand is
# non-negative, so truncating division is floor division.


def _div(a, b: int):
    return a // b if isinstance(a, int) else jax.lax.div(a, jnp.int32(b))


def _lower(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.minimum(a, b)


def _upper(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.maximum(a, b)


def _ordered(lo, full_lo, full_hi, hi):
    lo = _lower(lo, hi)
    full_lo = _lower(_upper(full_lo, lo), hi)
    full_hi = _lower(_upper(full_hi, full_lo), hi)
    return lo, full_lo, full_hi, hi


def key_tile_ranges(qi, block_q: int, block_k: int, num_k: int, causal: bool,
                    window):
    """``(lo, full_lo, full_hi, hi)`` for query tile ``qi``: key tiles
    ``[lo, hi)`` meet the band; of those ``[full_lo, full_hi)`` lie wholly
    inside it and need no mask; ``[lo, full_lo)`` straddle the window's
    left edge and ``[full_hi, hi)`` the diagonal."""
    if not causal:
        return 0, 0, num_k, num_k
    q0 = qi * block_q
    q1 = q0 + (block_q - 1)
    hi = _lower(_div(q1, block_k) + 1, num_k)       # kj·bk ≤ q1
    full_hi = _div(q0 + 1, block_k)                 # (kj+1)·bk − 1 ≤ q0
    if window is None:
        return 0, 0, _lower(full_hi, hi), hi
    lo = _div(_upper(q0 - (window - 1), 0), block_k)    # (kj+1)·bk − 1 > q0 − w
    full_lo = _div(_upper(q1 - (window - 1), 0) + (block_k - 1),
                   block_k)                             # kj·bk > q1 − w
    return _ordered(lo, full_lo, full_hi, hi)


def query_tile_ranges(kj, block_q: int, block_k: int, num_q: int,
                      causal: bool, window):
    """:func:`key_tile_ranges` seen from key tile ``kj``: query tiles
    ``[lo, hi)`` meet the band, ``[full_lo, full_hi)`` need no mask,
    ``[lo, full_lo)`` straddle the diagonal and ``[full_hi, hi)`` the
    window's left edge."""
    if not causal:
        return 0, 0, num_q, num_q
    k0 = kj * block_k
    k1 = k0 + (block_k - 1)
    lo = _div(k0, block_q)                              # qi·bq + bq − 1 ≥ k0
    full_lo = _div(k1 + (block_q - 1), block_q)         # qi·bq ≥ k1
    if window is None:
        return _lower(lo, num_q), _lower(full_lo, num_q), num_q, num_q
    hi = _lower(_div(k1 + (window - 1), block_q) + 1, num_q)
    full_hi = _div(k0 + window, block_q)            # qi·bq + bq − 1 < k0 + w
    return _ordered(lo, full_lo, full_hi, hi)


def _positions(q0, k0, block_q: int, block_k: int, transposed: bool = False):
    """Absolute (q_pos, k_pos) int32 grids of one tile: (block_q, block_k),
    or (block_k, block_q) for the backward's transposed tiles."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape,
                                          1 if transposed else 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape,
                                          0 if transposed else 1)
    return q_pos, k_pos


def _band_mask(q_pos, k_pos, window):
    mask = k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return mask


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    """How the kernels tile and walk one attention shape (module docstring)."""
    block_q: int
    block_k: int
    bwd_block_q: int
    bwd_block_k: int
    resident: bool          # forward: a head's K/V held in VMEM
    q_rows: int             # query rows of one forward grid step
    heads_per_step: int     # resident forward and fused backward
    fused_bwd: bool

    def describe(self) -> str:
        return (f"bq={self.block_q} bk={self.block_k} "
                f"bwd_bq={self.bwd_block_q} bwd_bk={self.bwd_block_k} "
                f"{'resident' if self.resident else 'chunked'} "
                f"q_rows={self.q_rows} "
                f"{'fused_bwd' if self.fused_bwd else 'split_bwd'} "
                f"heads_per_step={self.heads_per_step}")


def _padded(rows: int, D: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, D) array: lanes pad to 128."""
    return rows * -(-D // _LANES) * _LANES * itemsize


def _fwd_resident_bytes(q_rows, S, D, itemsize, heads, kv_heads, bq, bk):
    blocks = (2 * heads * _padded(q_rows, D, itemsize)          # q, o
              + 2 * kv_heads * _padded(S, D, itemsize)          # k, v
              + heads * _padded(q_rows, 1, 4))                  # lse (…, 1)
    scratch = 2 * bq * _LANES * 4 + _padded(bq, D, 4)
    return 2 * blocks + scratch + 4 * bq * bk * 4


def _bwd_fused_bytes(T, S, D, itemsize, heads, kv_heads, bq, bk):
    blocks = (3 * heads * _padded(T, D, itemsize)               # q, dO, dq
              + 2 * kv_heads * _padded(S, D, itemsize)          # k, v
              + 2 * heads * _padded(S, D, itemsize)             # dk, dv
              + 2 * heads * 8 * T * 4)                          # lse, δ rows
    scratch = (_padded(T, D, 4) + _padded(T, D, itemsize)       # dq, scaled q
               + 2 * _padded(bk, D, 4))
    return 2 * blocks + scratch + 6 * bq * bk * 4


def _kv_heads_per_step(heads_per_step: int, group: int) -> int:
    return max(heads_per_step // group, 1)


def plan_flash(T: int, S: int, D: int, itemsize: int, causal: bool = True,
               window=None, *, heads: int = 1, group: int = 1,
               block_q: int | None = None, block_k: int | None = None,
               vmem_budget: int = VMEM_BUDGET) -> FlashPlan:
    """The plan for ``(T, S, D, itemsize, causal, window)`` under
    ``vmem_budget`` bytes; ``heads`` query heads in groups of ``group`` per
    K/V head bound the heads a grid step may own.  ``block_q``/``block_k``
    override the tile sizes of both directions (tests)."""
    fq, fk = (block_q or _FWD_TILE[0]), (block_k or _FWD_TILE[1])
    gq, gk = (block_q or _BWD_TILE[0]), (block_k or _BWD_TILE[1])
    fq, gq = _largest_dividing_block(T, fq), _largest_dividing_block(T, gq)
    fk, gk = _largest_dividing_block(S, fk), _largest_dividing_block(S, gk)
    if T % fq or S % fk or T % gq or S % gk:
        # blocks must tile the sequence exactly — otherwise tail queries
        # would never be written and tail keys never attended
        raise ValueError(f"flash_attention requires T%{fq}==0 and "
                         f"S%{fk}==0; got T={T}, S={S}")

    # score elements of a head's band
    live = T * S
    if causal:
        live = T * S // 2 if window is None else T * min(window, S)

    def fwd_fits(hps, q_rows=T):
        return _fwd_resident_bytes(
            q_rows, S, D, itemsize, hps, _kv_heads_per_step(hps, group),
            fq, fk) <= vmem_budget

    def bwd_fits(hps):
        return _bwd_fused_bytes(
            T, S, D, itemsize, hps, _kv_heads_per_step(hps, group),
            gq, gk) <= vmem_budget

    fused_bwd = bwd_fits(1)
    # forward: the whole query length a step if that fits, else halved down
    # to one tile; chunked if a head's K/V do not fit beside even that
    q_rows = T
    while q_rows > fq and not fwd_fits(1, q_rows):
        q_rows //= 2
    resident = q_rows % fq == 0 and fwd_fits(1, q_rows)
    if not resident:
        q_rows = fq
    # heads a step owns: divisors of the heads that keep whole K/V heads to
    # a step, the fewest whose bands reach _STEP_SCORES within the budget
    hps = 1
    if q_rows == T:
        for cand in range(2, heads + 1):
            if hps * live >= _STEP_SCORES:
                break
            if heads % cand or (cand % group and group % cand):
                continue
            if not fwd_fits(cand) or (fused_bwd and not bwd_fits(cand)):
                break
            hps = cand
    return FlashPlan(block_q=fq, block_k=fk, bwd_block_q=gq, bwd_block_k=gk,
                     resident=resident, q_rows=q_rows, heads_per_step=hps,
                     fused_bwd=fused_bwd)


@functools.lru_cache(maxsize=None)
def _log_plan(T, S, D, plan: FlashPlan) -> None:
    """Once per distinct (shape, plan) of the process."""
    log.info("flash plan: T=%d S=%d D=%d %s", T, S, D, plan.describe())


def _record_plan(T, S, D, plan: FlashPlan) -> None:
    """The counter that says which plan a traced program was built with:
    an INFO line per distinct (shape, plan) and, each time a program traces
    an attention layer, a ``penroz/flash_plan`` span under whatever span is
    compiling (a /train/ job's first epochs, beside ``penroz/compile``)."""
    _log_plan(T, S, D, plan)
    with tracing.span("penroz/flash_plan", T=T, S=S, D=D, **dataclasses.asdict(plan)):
        pass


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _scaled(x, sm_scale: float):
    """``x·sm_scale`` in f32, cast back to the matmul operand dtype."""
    return (x.astype(jnp.float32) * sm_scale).astype(x.dtype)


def _scores(q, k, q0, k0, slope, *, masked: bool, window, positions: bool,
            transposed: bool = False):
    """Scores of one tile as forward and backward both see them — ``q``
    carries the softmax scale — with ALiBi (``slope`` not None) and, where
    the band's edge crosses the tile (``masked``), the band mask applied.
    (block_q, block_k), or (block_k, block_q) = k·qᵀ when ``transposed``.
    Returns ``(s, mask or None, (q_pos, k_pos) or None)``; ``positions``
    asks for the last whatever the rest needs (dropout)."""
    s = _dot(k, q, (1, 1)) if transposed else _dot(q, k, (1, 1))
    pos = mask = None
    if masked or positions or slope is not None:
        pos = q_pos, k_pos = _positions(q0, k0, q.shape[0], k.shape[0],
                                        transposed)
    if slope is not None:
        # ALiBi: per-head linear position bias slope·(k−q), ≤ 0 in the
        # causal region; slopes ride SMEM like the dropout seed.
        s = s + slope * (k_pos - q_pos).astype(jnp.float32)
    if masked:
        mask = _band_mask(q_pos, k_pos, window)
        s = jnp.where(mask, s, _NEG_INF)
    return s, mask, pos


def _fwd_tile(q, k, v, m_scr, l_scr, acc_scr, q0, k0, slope, seed, *,
              masked: bool, window, dropout_rate: float):
    """Online-softmax update of (m, l, acc) with one (block_q, block_k)
    tile.  ``masked``: the band's edge crosses this tile.  ``slope``/
    ``seed``: None without ALiBi / dropout."""
    block_k = k.shape[0]
    s, mask, pos = _scores(q, k, q0, k0, slope, masked=masked, window=window,
                           positions=dropout_rate > 0.0)
    # (m, l) stay lane-replicated (block_q, 128) tiles from scratch to
    # scratch: a (block_q,) or (block_q, 1) value costs a relayout at every
    # broadcast against the scores, and that — not the matmuls — was the
    # forward's time (CHANGES.md PR 26)
    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - _lanes(m_new, block_k))
    if masked and window is not None:
        # _NEG_INF is finite (-1e30): a row whose window lies entirely
        # outside this tile has s == m_new == -1e30 and exp(s - m_new)
        # would be 1, not 0 — zero masked entries explicitly.
        p = jnp.where(mask, p, 0.0)
    l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new
    if dropout_rate > 0.0:
        # l accumulates the *undropped* probabilities (dropout applies
        # after softmax normalization); only the V-contraction drops.
        keep = _keep_mask(*pos, seed, dropout_rate)
        p = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
    acc_scr[...] = (acc_scr[...] * _lanes(alpha, acc_scr.shape[1])
                    + _dot(p.astype(v.dtype), v, (1, 0)))


def _lanes(x, n: int):
    """A lane-replicated (rows, 128) tile as (rows, n)."""
    if n <= _LANES:
        return x if n == _LANES else x[:, :n]
    if n % _LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return pltpu.repeat(x, n // _LANES, axis=1)


def _fwd_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _fwd_result(m_scr, l_scr, acc_scr, dtype):
    l = l_scr[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return ((acc_scr[...] / _lanes(l_safe, acc_scr.shape[1])).astype(dtype),
            (m_scr[...] + jnp.log(l_safe))[:, :1])


def _loop(lo, hi, body):
    """``for i in [lo, hi): body(i)``: nothing or one call where the bounds
    are Python ints that say so, a ``fori_loop`` otherwise."""
    if isinstance(lo, int) and isinstance(hi, int) and hi - lo <= 1:
        if hi > lo:
            body(lo)
        return

    def step(i, carry):
        body(i)
        return carry

    jax.lax.fori_loop(lo, hi, step, 0)


def _walk(ranges, body):
    """``body(tile, masked)`` over the live tiles of one band row or column
    in ascending order; a walk whose bounds are statically empty (no window,
    no mask at all) is not traced."""
    lo, full_lo, full_hi, hi = ranges
    _loop(lo, full_lo, lambda i: body(i, True))
    _loop(full_lo, full_hi, lambda i: body(i, False))
    _loop(full_hi, hi, lambda i: body(i, True))


def _when_live(ranges, tile, step, causal: bool):
    """The chunked kernels' form of :func:`_walk`: this grid step's ``tile``
    runs ``step(masked)`` if it is live, and nothing if not."""
    lo, full_lo, full_hi, hi = ranges
    inside = (tile >= full_lo) & (tile < full_hi)
    pl.when(inside)(lambda: step(False))
    if causal:
        pl.when((tile >= lo) & (tile < hi) & ~inside)(lambda: step(True))


def _tile(i, block: int):
    if isinstance(i, int):
        return pl.ds(i * block, block)
    return pl.ds(pl.multiple_of(i * block, block), block)


def _fwd_resident_kernel(seed_ref, alibi_ref, q_ref, k_ref, v_ref, o_ref,
                         lse_ref, m_scr, l_scr, acc_scr, *, causal: bool,
                         sm_scale: float, block_q: int, block_k: int,
                         num_k: int, num_heads: int, heads_per_step: int,
                         group: int, dropout_rate: float, window,
                         use_alibi: bool):
    b, hs, qr = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tiles_per_step = q_ref.shape[2] // block_q

    def head(hh):
        h = hs * heads_per_step + hh
        hkv = hh // group
        slope, seed = _head_operands(seed_ref, alibi_ref, b, h, num_heads,
                                     use_alibi, dropout_rate)

        def query_tile(qt):
            qi = qr * tiles_per_step + qt
            rows = _tile(qt, block_q)
            q = _scaled(q_ref[0, hh, rows, :], sm_scale)
            _fwd_init(m_scr, l_scr, acc_scr)

            def key_tile(kj, masked):
                cols = _tile(kj, block_k)
                _fwd_tile(q, k_ref[0, hkv, cols, :], v_ref[0, hkv, cols, :],
                          m_scr, l_scr, acc_scr, qi * block_q, kj * block_k,
                          slope, seed, masked=masked, window=window,
                          dropout_rate=dropout_rate)

            _walk(key_tile_ranges(qi, block_q, block_k, num_k, causal,
                                  window), key_tile)
            out, lse = _fwd_result(m_scr, l_scr, acc_scr, o_ref.dtype)
            o_ref[0, hh, rows, :] = out
            lse_ref[0, hh, rows, :] = lse

        _loop(0, tiles_per_step, query_tile)

    _loop(0, heads_per_step, head)


def _fwd_chunked_kernel(seed_ref, alibi_ref, q_ref, k_ref, v_ref, o_ref,
                        lse_ref, m_scr, l_scr, acc_scr, qs_scr, *,
                        causal: bool, sm_scale: float, block_q: int,
                        block_k: int, num_k: int, num_heads: int,
                        dropout_rate: float, window, use_alibi: bool):
    b, h, qi, kj = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                    pl.program_id(3))

    @pl.when(kj == 0)
    def _init():
        _fwd_init(m_scr, l_scr, acc_scr)
        qs_scr[...] = _scaled(q_ref[0, 0], sm_scale)


    def step(masked):
        _fwd_tile(qs_scr[...], k_ref[0, 0], v_ref[0, 0], m_scr, l_scr,
                  acc_scr, qi * block_q, kj * block_k,
                  *_head_operands(seed_ref, alibi_ref, b, h, num_heads,
                                  use_alibi, dropout_rate),
                  masked=masked, window=window, dropout_rate=dropout_rate)

    _when_live(key_tile_ranges(qi, block_q, block_k, num_k, causal, window),
               kj, step, causal)

    @pl.when(kj == num_k - 1)
    def _finish():
        out, lse = _fwd_result(m_scr, l_scr, acc_scr, o_ref.dtype)
        o_ref[0, 0] = out
        lse_ref[0, 0] = lse


def _smem_operands(seed, alibi):
    seed = (jnp.zeros((1,), jnp.int32) if seed is None
            else jnp.asarray(seed, jnp.int32).reshape((1,)))
    alibi_arr = (jnp.asarray(alibi, jnp.float32) if alibi is not None
                 else jnp.zeros((1,), jnp.float32))
    return seed, alibi_arr


def _live_share(causal: bool) -> float:
    return 0.5 if causal else 1.0


def _clamped(ranges_fn, *args):
    """Index-map helper of the chunked kernels: the streamed tile index,
    clamped into the live range of the resident tile — a dead grid step
    names a block that is already in VMEM and moves no data."""
    def clamp(resident_tile, streamed_tile):
        lo, _, _, hi = ranges_fn(resident_tile, *args)
        return _lower(_upper(streamed_tile, lo), hi - 1)
    return clamp


def _flash_forward(q, k, v, causal: bool = True,
                   block_q: int | None = None, block_k: int | None = None,
                   dropout_rate: float = 0.0, seed=None,
                   interpret: bool = False, return_lse: bool = False,
                   window=None, alibi=None, scale=None,
                   plan: FlashPlan | None = None):
    B, Hq, T, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    group = Hq // Hkv
    if plan is None:
        plan = plan_flash(T, S, D, q.dtype.itemsize, causal, window,
                          heads=Hq, group=group, block_q=block_q,
                          block_k=block_k)
    block_q, block_k = plan.block_q, plan.block_k
    sm_scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    num_k = S // block_k
    seed, alibi_arr = _smem_operands(seed, alibi)
    common = dict(causal=causal, sm_scale=sm_scale, block_q=block_q,
                  block_k=block_k, num_k=num_k, num_heads=Hq,
                  dropout_rate=dropout_rate, window=window,
                  use_alibi=alibi is not None)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    stats = [pltpu.VMEM((block_q, _LANES), jnp.float32),
             pltpu.VMEM((block_q, _LANES), jnp.float32),
             pltpu.VMEM((block_q, D), jnp.float32)]
    if plan.resident:
        hps = plan.heads_per_step
        kvh = _kv_heads_per_step(hps, group)
        kernel = functools.partial(_fwd_resident_kernel, heads_per_step=hps,
                                   group=group, **common)
        grid = (B, Hq // hps, T // plan.q_rows)
        q_spec = pl.BlockSpec((1, hps, plan.q_rows, D),
                              lambda b, h, i: (b, h, i, 0))
        kv_spec = pl.BlockSpec((1, kvh, S, D),
                               lambda b, h, i: (b, h * hps // (group * kvh),
                                                0, 0))
        lse_spec = pl.BlockSpec((1, hps, plan.q_rows, 1),
                                lambda b, h, i: (b, h, i, 0))
        scratch = stats
        semantics = ("parallel", "parallel", "parallel")
    else:
        kernel = functools.partial(_fwd_chunked_kernel, **common)
        grid = (B, Hq, T // block_q, num_k)
        clamp = _clamped(key_tile_ranges, block_q, block_k, num_k, causal,
                         window)
        q_spec = pl.BlockSpec((1, 1, block_q, D),
                              lambda b, h, i, j: (b, h, i, 0))
        kv_spec = pl.BlockSpec((1, 1, block_k, D),
                               lambda b, h, i, j: (b, h // group,
                                                   clamp(i, j), 0))
        lse_spec = pl.BlockSpec((1, 1, block_q, 1),
                                lambda b, h, i, j: (b, h, i, 0))
        scratch = stats + [pltpu.VMEM((block_q, D), q.dtype)]
        semantics = ("parallel", "parallel", "parallel", "arbitrary")
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[smem, smem, q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            # (…, 1) trailing lane: Mosaic requires the last two block dims
            # be (8, 128)-divisible or equal to the array dims.
            jax.ShapeDtypeStruct((B, Hq, T, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * B * Hq * T * S * D * _live_share(causal)),
            bytes_accessed=int((q.size + k.size + v.size + q.size)
                               * q.dtype.itemsize),
            transcendentals=int(B * Hq * T * S * _live_share(causal))),
        interpret=interpret,
    )(seed, alibi_arr, q, k, v)
    return (out, lse) if return_lse else out


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _recompute_probs(q, k, lse, q0, k0, slope, seed, *, masked: bool,
                     window, dropout_rate: float, transposed: bool = False):
    """Normalized probabilities p (and the dropout keep-scale, or None) of
    one tile, identical to the forward's math.  ``transposed``: the tile is
    (block_k, block_q) and ``lse`` a (1, block_q) row; else (block_q,
    block_k) and a column."""
    s, mask, pos = _scores(q, k, q0, k0, slope, masked=masked, window=window,
                           positions=dropout_rate > 0.0,
                           transposed=transposed)
    p = jnp.exp(s - lse)
    if masked and window is not None:
        # rows fully outside the window in this tile have lse == -1e30 too;
        # exp(s - lse) would be 1 — zero masked entries explicitly
        p = jnp.where(mask, p, 0.0)
    drop_scale = None
    if dropout_rate > 0.0:
        keep = _keep_mask(*pos, seed, dropout_rate)
        drop_scale = jnp.where(keep, 1.0 / (1.0 - dropout_rate), 0.0)
    return p, drop_scale


def _bwd_fused_kernel(seed_ref, alibi_ref, q_ref, k_ref, v_ref, lse_ref,
                      delta_ref, do_ref, dq_ref, dk_ref, dv_ref,
                      qs_scr, dq_scr, dk_scr, dv_scr, *, causal: bool,
                      sm_scale: float, block_q: int, block_k: int,
                      num_heads: int, heads_per_step: int, group: int,
                      dropout_rate: float, window, use_alibi: bool):
    """One pass over the live tiles of ``heads_per_step`` heads, key tiles
    outermost, on transposed (block_k, block_q) tiles: dV and dK of a key
    tile accumulate in scratch over its query tiles, dQ of the whole head
    in a (T, D) f32 scratch that is written once."""
    b, hs = pl.program_id(0), pl.program_id(1)
    num_q = q_ref.shape[2] // block_q
    num_k = k_ref.shape[2] // block_k

    def head(hh):
        h = hs * heads_per_step + hh
        hkv = hh // group
        slope, seed = _head_operands(seed_ref, alibi_ref, b, h, num_heads,
                                     use_alibi, dropout_rate)
        qs_scr[...] = _scaled(q_ref[0, hh], sm_scale)
        dq_scr[...] = jnp.zeros_like(dq_scr)

        def key_tile(kj):
            cols = _tile(kj, block_k)
            k = k_ref[0, hkv, cols, :]
            v = v_ref[0, hkv, cols, :]
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

            def query_tile(qi, masked):
                rows = _tile(qi, block_q)
                q = qs_scr[rows, :]
                do = do_ref[0, hh, rows, :]
                p, drop_scale = _recompute_probs(
                    q, k, lse_ref[0, hh, :, rows], qi * block_q,
                    kj * block_k, slope, seed, masked=masked, window=window,
                    dropout_rate=dropout_rate, transposed=True)
                dp = _dot(v, do, (1, 1))                  # (dO·Vᵀ)ᵀ
                if drop_scale is not None:
                    dp = dp * drop_scale
                    p_drop = p * drop_scale
                else:
                    p_drop = p
                dv_scr[...] += _dot(p_drop.astype(do.dtype), do, (1, 0))
                ds = (p * (dp - delta_ref[0, hh, :, rows])).astype(q.dtype)
                dk_scr[...] += _dot(ds, q, (1, 0))        # q holds sm_scale
                dq_scr[rows, :] += _dot(ds, k, (0, 0))    # scaled at the end

            _walk(query_tile_ranges(kj, block_q, block_k, num_q, causal,
                                    window), query_tile)
            dk_ref[0, hh, cols, :] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[0, hh, cols, :] = dv_scr[...].astype(dv_ref.dtype)

        _loop(0, num_k, key_tile)
        dq_ref[0, hh] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)

    _loop(0, heads_per_step, head)


def _dq_kernel(seed_ref, alibi_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref,
               do_ref, dq_ref, dq_scr, qs_scr, *, causal: bool,
               sm_scale: float, block_q: int, block_k: int, num_k: int,
               num_heads: int, dropout_rate: float, window,
               use_alibi: bool):
    b, h, qi, kj = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                    pl.program_id(3))

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        qs_scr[...] = _scaled(q_ref[0, 0], sm_scale)


    def step(masked):
        k = k_ref[0, 0]
        p, drop_scale = _recompute_probs(
            qs_scr[...], k, lse_ref[0, 0], qi * block_q, kj * block_k,
            *_head_operands(seed_ref, alibi_ref, b, h, num_heads, use_alibi,
                            dropout_rate),
            masked=masked, window=window, dropout_rate=dropout_rate)
        dp = _dot(do_ref[0, 0], v_ref[0, 0], (1, 1))
        if drop_scale is not None:
            dp = dp * drop_scale
        ds = p * (dp - delta_ref[0, 0])
        dq_scr[...] += _dot(ds.astype(k.dtype), k, (1, 0))

    _when_live(key_tile_ranges(qi, block_q, block_k, num_k, causal, window),
               kj, step, causal)

    @pl.when(kj == num_k - 1)
    def _finish():
        dq_ref[0, 0] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, alibi_ref, q_ref, k_ref, v_ref, lse_ref,
                delta_ref, do_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                causal: bool, sm_scale: float, block_q: int, block_k: int,
                num_q: int, num_heads: int, dropout_rate: float, window,
                use_alibi: bool):
    b, h, kj, qi = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                    pl.program_id(3))

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)


    def step(masked):
        q = _scaled(q_ref[0, 0], sm_scale)
        do = do_ref[0, 0]
        p, drop_scale = _recompute_probs(
            q, k_ref[0, 0], lse_ref[0, 0], qi * block_q, kj * block_k,
            *_head_operands(seed_ref, alibi_ref, b, h, num_heads, use_alibi,
                            dropout_rate),
            masked=masked, window=window, dropout_rate=dropout_rate)
        p_drop = p if drop_scale is None else p * drop_scale
        dv_scr[...] += _dot(p_drop.astype(do.dtype), do, (0, 0))  # p̃ᵀ·dO
        dp = _dot(do, v_ref[0, 0], (1, 1))
        if drop_scale is not None:
            dp = dp * drop_scale
        ds = p * (dp - delta_ref[0, 0])
        dk_scr[...] += _dot(ds.astype(q.dtype), q, (0, 0))        # dSᵀ·Q

    _when_live(query_tile_ranges(kj, block_q, block_k, num_q, causal,
                                 window), qi, step, causal)

    @pl.when(qi == num_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal: bool, plan: FlashPlan,
                    dropout_rate: float, seed, interpret: bool = False,
                    window=None, alibi=None, scale=None):
    B, Hq, T, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    group = Hq // Hkv
    block_q, block_k = plan.bwd_block_q, plan.bwd_block_k
    sm_scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    num_q, num_k = T // block_q, S // block_k
    seed, alibi_arr = _smem_operands(seed, alibi)

    # δ_i = Σ_d dO_id · O_id — the softmax-backward row term; O(B·H·T·D),
    # cheap enough to fuse outside the kernels.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    common = dict(causal=causal, sm_scale=sm_scale, block_q=block_q,
                  block_k=block_k, num_heads=Hq, dropout_rate=dropout_rate,
                  window=window, use_alibi=alibi is not None)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    flops = int(10 * B * Hq * T * S * D * _live_share(causal))
    exps = int(B * Hq * T * S * _live_share(causal))
    dkv_shape = [jax.ShapeDtypeStruct((B, Hq, S, D), k.dtype),
                 jax.ShapeDtypeStruct((B, Hq, S, D), v.dtype)]

    if plan.fused_bwd:
        hps = plan.heads_per_step
        kvh = _kv_heads_per_step(hps, group)
        q_spec = pl.BlockSpec((1, hps, T, D), lambda b, h: (b, h, 0, 0))
        kv_spec = pl.BlockSpec((1, kvh, S, D),
                               lambda b, h: (b, h * hps // (group * kvh),
                                             0, 0))
        row_spec = pl.BlockSpec((1, hps, 1, T), lambda b, h: (b, h, 0, 0))
        dkv_spec = pl.BlockSpec((1, hps, S, D), lambda b, h: (b, h, 0, 0))
        dq, dk_ph, dv_ph = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, heads_per_step=hps,
                              group=group, **common),
            grid=(B, Hq // hps),
            in_specs=[smem, smem, q_spec, kv_spec, kv_spec, row_spec,
                      row_spec, q_spec],
            out_specs=[q_spec, dkv_spec, dkv_spec],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] + dkv_shape,
            scratch_shapes=[pltpu.VMEM((T, D), q.dtype),
                            pltpu.VMEM((T, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            cost_estimate=pl.CostEstimate(
                flops=flops, transcendentals=exps,
                bytes_accessed=int((3 * q.size + 2 * k.size
                                    + 2 * B * Hq * S * D)
                                   * q.dtype.itemsize)),
            interpret=interpret,
            # lse and δ as lane-dense (1, T) rows of the transposed tiles
        )(seed, alibi_arr, q, k, v, lse.reshape(B, Hq, 1, T),
          delta.reshape(B, Hq, 1, T), g)
    else:
        clamp_k = _clamped(key_tile_ranges, block_q, block_k, num_k, causal,
                           window)
        q_spec = pl.BlockSpec((1, 1, block_q, D),
                              lambda b, h, i, j: (b, h, i, 0))
        kv_spec = pl.BlockSpec((1, 1, block_k, D),
                               lambda b, h, i, j: (b, h // group,
                                                   clamp_k(i, j), 0))
        row_spec = pl.BlockSpec((1, 1, block_q, 1),
                                lambda b, h, i, j: (b, h, i, 0))
        semantics = pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary"))
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, num_k=num_k, **common),
            grid=(B, Hq, num_q, num_k),
            in_specs=[smem, smem, q_spec, kv_spec, kv_spec, row_spec,
                      row_spec, q_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                            pltpu.VMEM((block_q, D), q.dtype)],
            compiler_params=semantics,
            cost_estimate=pl.CostEstimate(
                flops=flops // 2, transcendentals=exps,
                bytes_accessed=int((3 * q.size + 2 * k.size)
                                   * q.dtype.itemsize)),
            interpret=interpret,
        )(seed, alibi_arr, q, k, v, lse, delta, g)

        # K/V-resident kernel: Q, dO, lse, δ stream through the inner grid.
        # index maps take (b, h, kj, qi) — q-row specs select on qi (dim 3).
        clamp_q = _clamped(query_tile_ranges, block_q, block_k, num_q,
                           causal, window)
        q_stream = pl.BlockSpec((1, 1, block_q, D),
                                lambda b, h, j, i: (b, h, clamp_q(j, i), 0))
        kv_res = pl.BlockSpec((1, 1, block_k, D),
                              lambda b, h, j, i: (b, h // group, j, 0))
        row_stream = pl.BlockSpec((1, 1, block_q, 1),
                                  lambda b, h, j, i: (b, h, clamp_q(j, i),
                                                      0))
        dkv_out = pl.BlockSpec((1, 1, block_k, D),
                               lambda b, h, j, i: (b, h, j, 0))
        dk_ph, dv_ph = pl.pallas_call(
            functools.partial(_dkv_kernel, num_q=num_q, **common),
            grid=(B, Hq, num_k, num_q),
            in_specs=[smem, smem, q_stream, kv_res, kv_res, row_stream,
                      row_stream, q_stream],
            out_specs=[dkv_out, dkv_out],
            out_shape=dkv_shape,
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32)],
            compiler_params=semantics,
            cost_estimate=pl.CostEstimate(
                flops=flops // 2, transcendentals=exps,
                bytes_accessed=int((3 * q.size + 4 * B * Hq * S * D)
                                   * q.dtype.itemsize)),
            interpret=interpret,
        )(seed, alibi_arr, q, k, v, lse, delta, g)

    if group > 1:
        dk = dk_ph.reshape(B, Hkv, group, S, D).sum(axis=2).astype(k.dtype)
        dv = dv_ph.reshape(B, Hkv, group, S, D).sum(axis=2).astype(v.dtype)
    else:
        dk = dk_ph.astype(k.dtype)
        dv = dv_ph.astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, seed, causal, plan, dropout_rate, interpret, window,
           alibi, scale=None):
    return _flash_forward(q, k, v, causal, dropout_rate=dropout_rate,
                          seed=seed, interpret=interpret, window=window,
                          alibi=alibi, scale=scale, plan=plan)


def _flash_fwd_rule(q, k, v, seed, causal, plan, dropout_rate, interpret,
                    window, alibi, scale=None):
    out, lse = _flash_forward(q, k, v, causal, dropout_rate=dropout_rate,
                              seed=seed, interpret=interpret,
                              return_lse=True, window=window, alibi=alibi,
                              scale=scale, plan=plan)
    return out, (q, k, v, seed, out, lse)


def _flash_bwd_rule(causal, plan, dropout_rate, interpret, window, alibi,
                    scale, residuals, g):
    q, k, v, seed, out, lse = residuals
    dq, dk, dv = _flash_backward(q, k, v, out, lse, g, causal, plan,
                                 dropout_rate, seed, interpret=interpret,
                                 window=window, alibi=alibi, scale=scale)
    return dq, dk, dv, np.zeros((), dtype=jax.dtypes.float0)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = True,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    dropout_rate: float = 0.0, seed=None,
                    interpret: bool = False, window=None, alibi=None,
                    scale=None, vmem_budget: int = VMEM_BUDGET):
    """Flash attention with a fused flash backward.

    q: (B, Hq, T, D); k, v: (B, Hkv, S, D) with Hq % Hkv == 0.
    ``dropout_rate`` > 0 applies post-softmax dropout inside the kernels
    (mask derived from ``seed`` — pass a fresh int32 scalar per step).
    ``window``: sliding-window width (causal only) — query t attends keys
    in ``(t - window, t]``; off-band tiles are never visited.

    Tile sizes, K/V residency, the backward's form and the heads a grid
    step owns come from :func:`plan_flash` on the shapes; ``block_q`` /
    ``block_k`` override the tile sizes and ``vmem_budget`` the bytes the
    plan may count on (a small one forces the chunked kernels).
    """
    B, Hq, T, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    if seed is None:
        seed = jnp.zeros((), jnp.int32)
    if alibi is not None:
        # static tuple: slopes are a pure function of the head count, so
        # baking them into the trace costs nothing and keeps the
        # custom_vjp arity fixed
        alibi = tuple(float(a) for a in np.asarray(alibi).reshape(-1))
        if len(alibi) != Hq:
            raise ValueError(f"alibi needs one slope per query head "
                             f"({Hq}), got {len(alibi)}")
    window = int(window) if window is not None else None
    plan = plan_flash(T, S, D, q.dtype.itemsize, bool(causal), window,
                      heads=Hq, group=Hq // Hkv,
                      block_q=int(block_q) if block_q else None,
                      block_k=int(block_k) if block_k else None,
                      vmem_budget=int(vmem_budget))
    _record_plan(T, S, D, plan)
    return _flash(q, k, v, jnp.asarray(seed, jnp.int32), bool(causal), plan,
                  float(dropout_rate), bool(interpret), window, alibi,
                  float(scale) if scale is not None else None)
