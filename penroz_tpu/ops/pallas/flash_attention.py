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

A tile the diagonal crosses is half dead, and at T = 1024 with 512-tiles two
of a head's three tiles are such.  Where tiles are square that tile starts
on the diagonal, so which of its sub-blocks are live is known when the
kernel is traced, and its body does them alone: each band of ``diag_grain``
keys against the queries from the diagonal down, static slices of the loaded
tile (:func:`_diag_bands`).  The tile stays the unit of the walk and of the
online softmax — no more grid steps or loop iterations, every row's (m, l,
acc) rescaled once a tile — which is why this gains where smaller tiles
lost (CHANGES.md PR 26, PR 38).  The forward (resident and chunked: one
function) and the one-pass backward do it, each at its own grain
(``FlashPlan.diag_grain``, ``.bwd_diag_grain``); the two-kernel backward
does its tiles whole.  ``FlashPlan.computed_over_live`` says what a walk
computes over what the band holds.

The backward recomputes probabilities from the forward's saved logsumexp:

- **fused** (a head's operands and its f32 dQ, with Mosaic's share on top,
  fit in the VMEM one call may ask the compiler for, ``VMEM_LIMIT``: to
  T = 8192 at D = 128 in bf16, T = 4096 at D = 256 or in f32): one kernel, key tiles outermost; each live tile's s, p, dp,
  ds are computed once (on the transposed tile, so logsumexp and δ arrive as
  lane-dense rows) and feed dV += p̃ᵀ·dO, dK += dSᵀ·Q and dQ += dS·K — five
  matmuls and one ``exp``.  A plan whose estimate is past the compiler's
  default share (``VMEM_BUDGET``: T ≥ 2048 at these head sizes) says so on
  its call (``vmem_limit_bytes``, ``FlashPlan.bwd_vmem_bytes``); one that
  fits compiles to the program it always was.
- otherwise (T ≥ 16384, or a caller's narrow budget) the two-kernel split:
  ``_dq_kernel`` (query tiles resident, K/V streaming) and ``_dkv_kernel``
  (key tiles resident, Q/dO streaming) — seven matmuls and two ``exp`` a
  tile, every tile on the diagonal whole.

GQA: per-query-head dK/dV, summed over the group outside the kernels.

**Two layouts, one set of kernels** (``FlashPlan.layout``).  The tile maths
above never looks at how a head is addressed; an indexer chosen by the
entry does that (:class:`_HeadMajor`, :class:`_LaneBlocks`: the same
methods, no flag).  One thing of the layout reaches the kernel bodies: the
split backward orients its tiles by the form the layout stores logsumexp
and δ in (``stat_rows``) — ``bhtd`` keeps the columns it had, so that its
programs are the ones they were; moving it to rows, as the one-pass
backward reads them in both layouts, would leave one orientation
(ROADMAP.md S2).

- ``bhtd`` — :func:`flash_attention`: q ``(B, Hq, T, D)``, k/v ``(B, Hkv, S,
  D)``; a head is a block index.  What sequence parallelism and callers with
  head-major arrays use.
- ``btd`` — :func:`flash_attention_btd`: the model's own layout.  q, k, v are
  lane ranges of ``(B, T, ·)`` arrays — the *same* fused ``(B, T, (Hq +
  2·Hkv)·D)`` projection for all three when nothing sits between the
  projection and the kernel (``fused_qkv``), separate ``(B, T, H·D)`` arrays
  otherwise — and ``o`` is ``(B, T, Hq·D)``: no head transposes around the
  kernels and no ``(…, 64)`` minor dim padded to 128 lanes in HBM.  Blocks
  are ``(1, rows, max(D, 128))``: at D = 64 a block holds two heads
  (``heads_per_block``), told apart by zeroing the other head's lanes in one
  matmul operand (a contraction of 64 or an output of 64 lanes already costs
  a full MXU pass, so the passes are the same count) and merging results
  with a lane select on ``(rows, 128)`` tiles.  Logsumexp and δ are
  lane-dense ``(B, Hq/heads_per_block, heads_per_block, T)`` rows.  Takes:
  D in {64, 128, 256}; ``heads_per_block`` dividing Hq and Hkv; at D = 64
  Hq = Hkv (a block's two query heads need their two K/V heads at the same
  lanes); any GQA group at D ≥ 128.  :func:`btd_refusal` says why not.

The Pallas calls are named ``penroz_flash_fwd``, ``penroz_flash_bwd`` (one
pass) and ``penroz_flash_bwd_dq`` / ``penroz_flash_bwd_dkv`` (the split) in
both layouts; ``btd`` adds ``penroz_flash_bwd_delta``, which sums δ = Σ dO·O
over each head's lanes (``bhtd`` leaves that to an XLA reduction).  The
benchmark finds the kernels in a device trace by these names
(``benchmark/metrics/penroz_flash_roofline.py``): a rename blinds it, and
``tests/test_tpu_compile.py`` fails first.

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
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from penroz_tpu.utils import tracing

log = logging.getLogger(__name__)

_NEG_INF = -1e30
_LANES = 128  # f32 scratch lane width for the (m, l) carries
_HEAD_SEED_PRIME = np.int32(0x632BE5A7)

# What a kernel's blocks, scratch and tile temporaries may take by the
# estimates below without asking: v5e's scoped-VMEM default is 16 MiB, the
# rest is Mosaic's.  The forward's residency and the heads a step owns are
# judged against it, and a backward that fits it passes no limit.
_SCOPED_DEFAULT = 16 * 2 ** 20
VMEM_BUDGET = 12 * 2 ** 20
# The most the one-pass backward's call may ask the compiler for
# (``vmem_limit_bytes``): a v5e core has 128 MiB of VMEM, the 16 MiB only the
# default share of one call; half of it leaves the rest to whatever XLA keeps
# resident around the call.  A plan asks for its estimate and Mosaic's share
# on top, in the proportion the budget has of the default (:func:`_asked`);
# past the limit the backward falls to the two kernels.  Mosaic's own need
# read 0.33–0.83 of the estimate in bf16 (17.5 MiB of 24.1 at T = 4096,
# D = 128) and up to 1.12 of it in f32 (T = 1024 with dropout, ALiBi and a
# window: 16.4 MiB, which the default does not hold either; CHANGES.md PR 42).
VMEM_LIMIT = 64 * 2 ** 20
# Tiles (block_q, block_k) by direction and score elements a grid step should
# cover (a step costs ~0.35 µs; a head of T = S = 1024, D = 64 takes ~5 µs
# forward): from the sweep on one v5e at (12, 12, 1024, 64) bf16 causal,
# CHANGES.md PR 26.
_FWD_TILE = (512, 512)
_BWD_TILE = (512, 512)
_STEP_SCORES = 2 ** 19
# Sub-blocks a tile on the diagonal is cut into, forward and one-pass
# backward (FlashPlan.diag_grain, .bwd_diag_grain): from the sweep on one v5e
# at (12, 12, 1024, 64) and (4, 8 on 2, 1024, 128) bf16 causal, CHANGES.md
# PR 38 — the backward's time is its matmuls and follows the area down to 128,
# the forward pays more for a band than 128 keys save it.
_DIAG_GRAIN = 256
_BWD_DIAG_GRAIN = 128
# What the forward rule calls its two results (``checkpoint_name``): the
# identity everywhere but under a ``jax.checkpoint`` whose policy saves these
# names (ops/modules.py::Looped), whose backward then finds ``o`` and the
# logsumexp kept and does not run ``penroz_flash_fwd`` a second time.
OUT_NAME = "penroz_flash_out"
LSE_NAME = "penroz_flash_lse"


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


def _clamp(x, lo, hi):
    return _lower(_upper(x, lo), hi)


def key_tile_ranges(qi, block_q: int, block_k: int, num_k: int, causal: bool,
                    window):
    """``(lo, full_lo, full_hi, hi)`` for query tile ``qi``: key tiles
    ``[lo, hi)`` meet the band; of those ``[full_lo, full_hi)`` lie wholly
    inside it and need no mask; ``[full_hi, hi)`` straddle the diagonal and
    ``[lo, full_lo)`` the window's left edge alone."""
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
    lo = _lower(lo, hi)
    full_hi = _clamp(full_hi, lo, hi)
    return lo, _clamp(full_lo, lo, full_hi), full_hi, hi


def query_tile_ranges(kj, block_q: int, block_k: int, num_q: int,
                      causal: bool, window):
    """:func:`key_tile_ranges` seen from key tile ``kj``: query tiles
    ``[lo, hi)`` meet the band, ``[full_lo, full_hi)`` need no mask,
    ``[lo, full_lo)`` straddle the diagonal and ``[full_hi, hi)`` the
    window's left edge alone."""
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
    lo = _lower(lo, hi)
    full_lo = _clamp(full_lo, lo, hi)
    return lo, full_lo, _clamp(full_hi, full_lo, hi), hi


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


# What a walk tells a tile's body about the band's edge (None: the tile lies
# wholly inside).  _DIAG: the diagonal crosses it, the window's left edge
# perhaps too; where tiles are square it is the one tile of its row and
# column that starts on the diagonal (q0 == k0), and its body may leave out
# the sub-blocks above the diagonal (:func:`_diag_bands`).  _EDGE: any other
# tile an edge crosses, masked as a whole.
_EDGE, _DIAG = "edge", "diag"


def _cuts_diagonal(edge, grain: int, block_q: int, block_k: int) -> bool:
    """Whether a tile's body does the tile by its live sub-blocks (a plan's
    grain is below the tile only where tiles are square; checked again here
    because only then does the _DIAG tile start on the diagonal)."""
    return edge == _DIAG and block_q == block_k and grain < block_q


def _diag_bands(block: int, grain: int, window):
    """The live part of a square ``block`` × ``block`` tile that starts on
    the diagonal, in bands of ``grain`` keys: ``(c, hi)`` for each — keys
    ``[c·grain, (c+1)·grain)`` of the tile meet its queries ``[c·grain,
    hi·grain)``, from the sub-block on the diagonal down to the last the
    window lets see them; what lies above the diagonal or wholly left of
    the window is in no band.  All static: the tile's place on the
    diagonal and the window are Python ints."""
    n = block // grain
    # sub-block (rq, c) is wholly left of the window from
    # (rq − c − 1)·grain + 1 ≥ window on
    reach = n if window is None else 1 + -(-(window - 1) // grain)
    return [(c, min(c + reach, n)) for c in range(n)]


def _grains(lo: int, hi: int, grain: int):
    return slice(lo * grain, hi * grain)


def computed_over_live(T: int, S: int, block_q: int, block_k: int,
                       grain: int, causal: bool, window) -> float:
    """Score elements the walk of one head computes ÷ those its band holds
    (``T·S/2`` causal, ``T·window`` windowed, as :func:`plan_flash` counts a
    band): 1 for a walk that computes nothing dead."""
    if not causal:
        return 1.0
    num_k = S // block_k
    on_diagonal = block_q * block_k
    if _cuts_diagonal(_DIAG, grain, block_q, block_k):
        on_diagonal = sum((hi - c) * grain * grain
                          for c, hi in _diag_bands(block_q, grain, window))
    computed = 0
    for qi in range(T // block_q):
        lo, _, full_hi, hi = key_tile_ranges(qi, block_q, block_k, num_k,
                                             causal, window)
        computed += ((full_hi - lo) * block_q * block_k
                     + (hi - full_hi) * on_diagonal)
    return computed / (T * S / 2 if window is None else T * min(window, S))


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
    # bytes the one-pass backward counts on (_bwd_fused_bytes of the heads a
    # step owns); past VMEM_BUDGET its call asks the compiler for them and
    # Mosaic's share (_asked).  0: the two-kernel backward, whose tiles fit
    # the default at any length
    bwd_vmem_bytes: int
    # a square tile on the diagonal is done in sub-blocks this wide, the
    # dead ones left out (_diag_bands), by the forward and by the one-pass
    # backward; = the tile's size: every tile is done whole
    diag_grain: int
    bwd_diag_grain: int
    # score elements computed ÷ live (computed_over_live), likewise
    computed_over_live: float
    bwd_computed_over_live: float
    layout: str = "bhtd"    # "bhtd" | "btd" (module docstring)
    heads_per_block: int = 1    # btd: heads a lane block holds
    fused_qkv: bool = False     # btd: q, k, v are ranges of one array

    def describe(self) -> str:
        text = (f"bq={self.block_q} bk={self.block_k} "
                f"bwd_bq={self.bwd_block_q} bwd_bk={self.bwd_block_k} "
                f"diag_grain={self.diag_grain} "
                f"bwd_diag_grain={self.bwd_diag_grain} "
                f"computed_over_live={self.computed_over_live:.3f} "
                f"bwd_computed_over_live={self.bwd_computed_over_live:.3f} "
                f"{'resident' if self.resident else 'chunked'} "
                f"q_rows={self.q_rows} "
                f"{'fused_bwd' if self.fused_bwd else 'split_bwd'} "
                f"bwd_vmem_mib={self.bwd_vmem_bytes / 2 ** 20:.1f} "
                f"heads_per_step={self.heads_per_step} "
                f"layout={self.layout}")
        if self.layout == "btd":
            text += f" heads_per_block={self.heads_per_block}"
        return text + (" fused_qkv" if self.fused_qkv else "")


def _padded(rows: int, D: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, D) array: lanes pad to 128."""
    return rows * -(-D // _LANES) * _LANES * itemsize


def _fwd_resident_bytes(q_rows, S, D, itemsize, heads, kv_heads, bq, bk,
                        Dv=None):
    """``D``: the width of q and k, ``Dv``: of v and o (default ``D``)."""
    Dv = D if Dv is None else Dv
    both = lambda rows, size: _padded(rows, D, size) + _padded(rows, Dv, size)
    blocks = (heads * both(q_rows, itemsize)                    # q, o
              + kv_heads * both(S, itemsize)                    # k, v
              + heads * _padded(q_rows, 1, 4))                  # lse (…, 1)
    scratch = 2 * bq * _LANES * 4 + _padded(bq, Dv, 4)
    return 2 * blocks + scratch + 4 * bq * bk * 4


def _bwd_fused_bytes(T, S, D, itemsize, heads, kv_heads, bq, bk, Dv=None):
    Dv = D if Dv is None else Dv
    both = lambda rows, size: _padded(rows, D, size) + _padded(rows, Dv, size)
    blocks = (heads * (_padded(T, D, itemsize) + both(T, itemsize))  # q, dq, dO
              + kv_heads * both(S, itemsize)                    # k, v
              + heads * both(S, itemsize)                       # dk, dv
              + 2 * heads * 8 * T * 4)                          # lse, δ rows
    scratch = (_padded(T, D, 4) + _padded(T, D, itemsize)       # dq, scaled q
               + 8 * T * 4 + both(bk, 4))               # lse and δ; dk, dv
    return 2 * blocks + scratch + 6 * bq * bk * 4


def _asked(estimate: int) -> int:
    """What a call whose blocks, scratch and temporaries are estimated at
    ``estimate`` bytes asks the compiler for: that and Mosaic's share."""
    return estimate * _SCOPED_DEFAULT // VMEM_BUDGET


def _heads_per_block(D: int) -> int:
    """Heads of size ``D`` a ``btd`` lane block — ``max(D, 128)`` lanes —
    holds."""
    return max(_LANES // D, 1)


def _diag_grain(preferred: int, block_q: int, block_k: int,
                cuts: bool) -> int:
    """``FlashPlan.diag_grain`` of one direction's tiles: ``preferred``, or
    the largest below it the tile is whole sub-blocks of, where the kernel
    ``cuts`` the tile on the diagonal and tiles are square, so that this
    tile starts on the diagonal; else the tile itself."""
    if cuts and block_q == block_k:
        return _largest_dividing_block(block_q, preferred)
    return block_q


def _kv_heads_per_step(heads_per_step: int, group: int) -> int:
    return max(heads_per_step // group, 1)


def plan_flash(T: int, S: int, D: int, itemsize: int, causal: bool = True,
               window=None, *, heads: int = 1, group: int = 1,
               block_q: int | None = None, block_k: int | None = None,
               vmem_budget: int = VMEM_BUDGET, layout: str = "bhtd",
               fused_qkv: bool = False, Dv: int | None = None) -> FlashPlan:
    """The plan for ``(T, S, D, itemsize, causal, window)`` under
    ``vmem_budget`` bytes; ``heads`` query heads in groups of ``group`` per
    K/V head bound the heads a grid step may own.  ``Dv`` (default ``D``):
    the width of v and o where it is not that of q and k (latent attention:
    192-wide scores over 128-wide values).  Such a pair takes the ``bhtd``
    layout and one product over the whole score width: a head 192 lanes
    wide starts on a 128-lane boundary every other head only, so lane blocks
    of ``(B, T, H·D)`` arrays cannot address it, and a ``(rows, 192)``
    operand pads to the 256 lanes two passes of the MXU take either way.  ``block_q``/``block_k``
    override the tile sizes of both directions (tests).  ``layout="btd"``:
    a step owns one lane block — ``max(D, 128)`` lanes, ``heads_per_block``
    heads — whose VMEM is that of one head as wide as the block.

    Whether the backward runs in one pass is judged against ``VMEM_LIMIT``,
    the most its call may ask for (:func:`_asked`); everything else against
    the budget.  A caller
    that narrows the budget below the default (tests, to reach the chunked
    and split kernels at small shapes) narrows that with it."""
    heads_per_block = 1
    Dv = D if Dv is None else Dv
    if layout == "btd":
        if Dv != D:
            raise ValueError(f"the btd layout takes one head width, got "
                             f"D={D}, Dv={Dv}")
        heads_per_block = _heads_per_block(D)
        D, heads, group = D * heads_per_block, 1, 1
        Dv = D
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
            fq, fk, Dv) <= vmem_budget

    def bwd_bytes(hps):
        return _bwd_fused_bytes(
            T, S, D, itemsize, hps, _kv_heads_per_step(hps, group), gq, gk,
            Dv)

    fused_bwd = (_asked(bwd_bytes(1)) <= VMEM_LIMIT
                 if vmem_budget >= VMEM_BUDGET
                 else bwd_bytes(1) <= vmem_budget)
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
            if not fwd_fits(cand) or (fused_bwd
                                      and bwd_bytes(cand) > vmem_budget):
                break
            hps = cand
    # the two-kernel backward does its tiles whole
    grain = _diag_grain(_DIAG_GRAIN, fq, fk, causal)
    bwd_grain = _diag_grain(_BWD_DIAG_GRAIN, gq, gk, causal and fused_bwd)
    return FlashPlan(block_q=fq, block_k=fk, bwd_block_q=gq, bwd_block_k=gk,
                     resident=resident, q_rows=q_rows,
                     heads_per_step=hps * heads_per_block,
                     fused_bwd=fused_bwd,
                     bwd_vmem_bytes=bwd_bytes(hps) if fused_bwd else 0,
                     diag_grain=grain,
                     bwd_diag_grain=bwd_grain,
                     computed_over_live=computed_over_live(
                         T, S, fq, fk, grain, causal, window),
                     bwd_computed_over_live=computed_over_live(
                         T, S, gq, gk, bwd_grain, causal, window),
                     layout=layout,
                     heads_per_block=heads_per_block, fused_qkv=fused_qkv)


@functools.lru_cache(maxsize=None)
def _log_plan(T, S, D, Dv, plan: FlashPlan) -> None:
    """Once per distinct (shape, plan) of the process; ``Dv`` is named where
    it is not ``D``."""
    widths = f"D={D}" if Dv == D else f"D={D} Dv={Dv}"
    log.info("flash plan: T=%d S=%d %s %s", T, S, widths, plan.describe())


def _record_plan(T, S, D, plan: FlashPlan, Dv: int | None = None) -> None:
    """The counter that says which plan a traced program was built with:
    an INFO line per distinct (shape, plan) and, each time a program traces
    an attention layer, a ``penroz/flash_plan`` span under whatever span is
    compiling (a /train/ job's first epochs, beside ``penroz/compile``)."""
    Dv = D if Dv is None else Dv
    _log_plan(T, S, D, Dv, plan)
    with tracing.span("penroz/flash_plan", T=T, S=S, D=D, Dv=Dv,
                      **dataclasses.asdict(plan)):
        pass


# ---------------------------------------------------------------------------
# the indexer: how a kernel finds a head in its blocks
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _HeadMajor:
    """The ``bhtd`` indexer.  An indexer says where head ``hh`` of a grid
    step lives in the step's blocks, which BlockSpecs bring those blocks in
    (``at(*grid ids) -> (batch, head block, row block)``), what shapes the
    results have and what XLA does around the kernels; :class:`_LaneBlocks`
    has the same methods (module docstring, "Two layouts").

    Blocks are ``(1, heads, rows, D)``; a head is an index and its K/V head
    ``hh // group``.  Logsumexp and δ are ``(B, H, T, 1)`` columns (Mosaic
    requires the last two block dims be (8, 128)-divisible or equal to the
    array's): the one-pass backward takes them reshaped to rows, the split
    backward as they are (``stat_rows``)."""
    D: int
    heads: int
    kv_heads: int
    Dv: int | None = None   # width of v, o, dO and dv; None: D

    layout = "bhtd"
    hpb = 1
    stat_rows = False       # split backward: (block_q, block_k) tiles

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads

    @property
    def width(self) -> int:
        return self.D

    @property
    def vwidth(self) -> int:
        """Lanes of a value-side block or scratch (v, o, dO, dv)."""
        return self.D if self.Dv is None else self.Dv

    # -- inside a kernel ----------------------------------------------------

    def rows(self, ref) -> int:
        return ref.shape[2]

    def get(self, ref, hh, rows):
        """Rows of a query-side block (q, dO) that hold head ``hh``."""
        return ref[0, hh, rows, :]

    def kv_head(self, hh):
        """Index of head ``hh``'s K/V head in the step's K/V blocks."""
        return hh // self.group

    def kv(self, ref, hkv, rows):
        return ref[0, hkv, rows, :]

    def own(self, x, hh):
        """``x`` as a matmul operand that contributes head ``hh`` alone."""
        return x

    def scaled_q(self, ref, hh, rows, sm_scale: float):
        """Head ``hh``'s queries times the softmax scale."""
        return _scaled(self.get(ref, hh, rows), sm_scale)

    def put(self, ref, hh, rows, value):
        ref[0, hh, rows, :] = value

    def lse_form(self, tile):
        """The forward's lane-replicated ``(rows, 128)`` logsumexp as it is
        stored: a ``(rows, 1)`` column."""
        return tile[:, :1]

    def put_lse(self, ref, hh, rows, lse):
        ref[0, hh, rows, :] = lse

    def row(self, ref, hh, rows):
        """``(1, rows)`` of a logsumexp / δ block stored as rows."""
        return ref[0, hh, :, rows]

    def stat(self, ref, hh):
        """A split-backward step's logsumexp / δ: a ``(block_q, 1)``
        column."""
        return ref[0, hh]

    # -- BlockSpecs ---------------------------------------------------------

    def spec(self, heads, rows, at, operand=None):
        """A query-head array's block as wide as q (q, dq, the per-head dk;
        ``operand``: see :meth:`_LaneBlocks.spec`)."""
        return pl.BlockSpec((1, heads, rows, self.D),
                            lambda *g: (*at(*g), 0))

    def vspec(self, heads, rows, at):
        """… as wide as v (o, dO, the per-head dv)."""
        return pl.BlockSpec((1, heads, rows, self.vwidth),
                            lambda *g: (*at(*g), 0))

    def kv_spec(self, heads, rows, at, operand):
        """K (``operand`` 1) or V (2): the K/V heads of the step's query
        heads."""
        kvh = _kv_heads_per_step(heads, self.group)

        def index(*g):
            b, h, r = at(*g)
            return b, h * heads // (self.group * kvh), r, 0

        return pl.BlockSpec(
            (1, kvh, rows, self.D if operand == 1 else self.vwidth), index)

    def stat_spec(self, heads, rows, at, as_rows: bool):
        if as_rows:
            return pl.BlockSpec((1, heads, 1, rows),
                                lambda *g: (*at(*g)[:2], 0, at(*g)[2]))
        return pl.BlockSpec((1, heads, rows, 1), lambda *g: (*at(*g), 0))

    # -- shapes, and what XLA does around the kernels -----------------------

    def dims(self, q, k):
        """``(B, T, S)`` of the operands."""
        return q.shape[0], q.shape[2], k.shape[2]

    def like_q(self, B, rows):
        """Shape of an array with ``rows`` positions of every query head, as
        wide as q."""
        return B, self.heads, rows, self.D

    def like_v(self, B, rows):
        """… as wide as v."""
        return B, self.heads, rows, self.vwidth

    def lse_shape(self, B, T):
        return B, self.heads, T, 1

    def as_rows(self, stat):
        """A logsumexp / δ array as the one-pass backward's transposed tiles
        want it: lane-dense ``(1, T)`` rows."""
        B, H, T, _ = stat.shape
        return stat.reshape(B, H, 1, T)

    def delta(self, out, g, block_q: int, interpret: bool):
        """δ_i = Σ_d dO_id · O_id — the softmax-backward row term; O(B·H·T·D),
        outside the backward kernels, shaped like the forward's logsumexp.
        Here an XLA reduction over the minor dim."""
        return jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                       axis=-1, keepdims=True)

    def sum_groups(self, x):
        """Per-query-head dK or dV summed over each K/V head's group."""
        B, _, S, D = x.shape
        return x.reshape(B, self.kv_heads, self.group, S, D).sum(axis=2)


@dataclasses.dataclass(frozen=True)
class _LaneBlocks:
    """The ``btd`` indexer (methods as :class:`_HeadMajor`).  Blocks are
    ``(1, rows, width)`` with ``width = max(D, 128)`` lanes holding ``hpb``
    heads; a head is a lane range, its K/V head the same range of the K/V
    block the index map chose.  ``fused``: q, k and v are lane ranges of one
    array.  Logsumexp and δ are lane-dense ``(B, heads / hpb, hpb, T)``
    rows, so every backward tile is transposed."""
    D: int
    heads: int
    kv_heads: int
    fused: bool

    layout = "btd"
    stat_rows = True        # split backward: (block_k, block_q) tiles

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads

    @property
    def hpb(self) -> int:
        return _heads_per_block(self.D)

    @property
    def width(self) -> int:
        return self.D * self.hpb

    vwidth = width          # one head width in this layout

    @property
    def offsets(self) -> tuple:
        """The first lane block of q, k and v in their arrays."""
        if not self.fused:
            return 0, 0, 0
        return (0, self.heads // self.hpb,
                (self.heads + self.kv_heads) // self.hpb)

    # -- inside a kernel ----------------------------------------------------

    def rows(self, ref) -> int:
        return ref.shape[1]

    def get(self, ref, hh, rows):
        return ref[0, rows, :]

    def kv_head(self, hh):
        return hh

    def kv(self, ref, hkv, rows):
        return ref[0, rows, :]

    def _own(self, shape, hh):
        """Lanes of a ``(rows, width)`` tile that are head ``hh``'s."""
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
        return (lane >= hh * self.D) & (lane < (hh + 1) * self.D)

    def own(self, x, hh):
        """``x`` with the lanes of the block's other heads zeroed: a
        contraction over the block's lanes then sums head ``hh`` alone."""
        if self.hpb == 1:
            return x
        return jnp.where(self._own(x.shape, hh), x, jnp.zeros_like(x))

    def scaled_q(self, ref, hh, rows, sm_scale: float):
        """:meth:`own` folded into the multiply by the softmax scale."""
        x = self.get(ref, hh, rows)
        if self.hpb == 1:
            return _scaled(x, sm_scale)
        scale = jnp.where(self._own((1, x.shape[-1]), hh), sm_scale, 0.0)
        return (x.astype(jnp.float32) * scale).astype(x.dtype)

    def put(self, ref, hh, rows, value):
        """Write head ``hh``'s ``(rows, width)`` result; the lanes of the
        block's other heads (theirs, or not yet written) stay as they are."""
        if self.hpb == 1:
            ref[0, rows, :] = value
        else:
            ref[0, rows, :] = jnp.where(self._own(value.shape, hh), value,
                                        ref[0, rows, :])

    def lse_form(self, tile):
        """… stored as a lane-dense ``(1, rows)`` row."""
        return tile.T[:1, :]

    def put_lse(self, ref, hh, rows, lse):
        ref[0, 0, pl.ds(hh, 1), rows] = lse

    def row(self, ref, hh, rows):
        return ref[0, 0, pl.ds(hh, 1), rows]

    def stat(self, ref, hh):
        """… a ``(1, block_q)`` row."""
        return ref[0, 0, pl.ds(hh, 1), :]

    # -- BlockSpecs ---------------------------------------------------------

    def _lane_block(self, rows, at, first, of_step):
        def index(*g):
            b, h, r = at(*g)
            return b, r, of_step(h) + first

        return pl.BlockSpec((1, rows, self.width), index)

    def spec(self, heads, rows, at, operand=None):
        """``operand`` 0 for q, whose lane range of a fused array starts at
        ``offsets[0]``; None for arrays of their own (o, dO, dq and the
        per-query-head dk, dv)."""
        first = 0 if operand is None else self.offsets[operand]
        return self._lane_block(rows, at, first, lambda h: h)

    def vspec(self, heads, rows, at):
        return self.spec(heads, rows, at)

    def kv_spec(self, heads, rows, at, operand):
        """K (``operand`` 1) or V (2)."""
        return self._lane_block(
            rows, at, self.offsets[operand],
            lambda h: h * self.hpb // self.group // self.hpb)

    def stat_spec(self, heads, rows, at, as_rows: bool):
        return pl.BlockSpec((1, 1, self.hpb, rows),
                            lambda *g: (*at(*g)[:2], 0, at(*g)[2]))

    # -- shapes, and what XLA does around the kernels -----------------------

    def dims(self, q, k):
        return q.shape[0], q.shape[1], k.shape[1]

    def like_q(self, B, rows):
        return B, rows, self.heads * self.D

    like_v = like_q

    def lse_shape(self, B, T):
        return B, self.heads // self.hpb, self.hpb, T

    def as_rows(self, stat):
        return stat

    def delta(self, out, g, block_q: int, interpret: bool):
        """… a kernel of its own, bandwidth-bound: XLA relays the whole f32
        product out to reduce over a 64-lane share of the minor dim, and
        inside the backward kernels the product is VPU work they have no
        room for (CHANGES.md PR 32)."""
        B, T, _ = out.shape
        rows = _largest_dividing_block(T, 2048)     # of block_q-row tiles
        at = lambda b, h, i: (b, h, i)
        block = self.spec(self.hpb, rows, at)
        return pl.pallas_call(
            functools.partial(_delta_kernel, ix=self, block_q=block_q),
            grid=(B, self.heads // self.hpb, T // rows),
            in_specs=[block, block],
            out_specs=self.stat_spec(self.hpb, rows, at, as_rows=True),
            out_shape=jax.ShapeDtypeStruct(self.lse_shape(B, T),
                                           jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            cost_estimate=pl.CostEstimate(
                flops=2 * out.size, transcendentals=0,
                bytes_accessed=2 * out.size * out.dtype.itemsize),
            interpret=interpret,
            name="penroz_flash_bwd_delta",
        )(out, g)

    def sum_groups(self, x):
        """… as adds of lane ranges, one fusion: a reshape that splits the
        lanes into ``(group, D)`` relays the whole array out first (0.29 of
        a 3.8 ms layer at 32 on 8 heads, T = 2048; PERF.md §6, PR 32)."""
        D, group = self.D, self.group
        head = lambda h: x[..., h * D:(h + 1) * D].astype(jnp.float32)
        return jnp.concatenate(
            [sum(head(kv * group + i) for i in range(group))
             for kv in range(self.kv_heads)], axis=-1).astype(x.dtype)


def _head_index(hs, heads_per_step: int, hh):
    """Head ``hh`` of grid step ``hs`` among all query heads."""
    return hs if heads_per_step == 1 else hs * heads_per_step + hh


def _per_head(heads: int, shape: tuple) -> tuple:
    """Shape of a chunked kernel's scratch that every head of the step's
    block needs its own of, across grid steps."""
    return shape if heads == 1 else (heads, *shape)


def _at(ref, hh, heads: int):
    return ref if heads == 1 else ref.at[hh]


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
              edge, grain: int, window, dropout_rate: float):
    """Online-softmax update of (m, l, acc) with one (block_q, block_k)
    tile.  ``edge``: where the band's edge crosses it (None, _EDGE, _DIAG).
    ``slope``/``seed``: None without ALiBi / dropout.

    A tile on the diagonal is done by its live sub-blocks: each band of
    ``grain`` keys against the queries from the diagonal down
    (:func:`_diag_bands`: the keys are the matmuls' stationary operand, so
    every band streams many rows through few of them — bands of query rows
    gained nothing, CHANGES.md PR 38).  The bands share one update: the row
    maxima of all are taken before any is exponentiated, so each row's
    (m, l, acc) is rescaled once, as by a whole tile."""
    block_q, block_k = q.shape[0], k.shape[0]
    if _cuts_diagonal(edge, grain, block_q, block_k):
        bands = [(_grains(c, hi, grain), _grains(c, c + 1, grain), True)
                 for c, hi in _diag_bands(block_q, grain, window)]
    else:
        bands = [(slice(0, block_q), slice(0, block_k), edge is not None)]
    # (m, l) stay lane-replicated (block_q, 128) tiles from scratch to
    # scratch: a (block_q,) or (block_q, 1) value costs a relayout at every
    # broadcast against the scores, and that — not the matmuls — was the
    # forward's time (CHANGES.md PR 26)
    m_prev = m_new = m_scr[...]
    scored = []
    for rows, cols, masked in bands:
        s, mask, pos = _scores(
            q[rows], k[cols], q0 + rows.start, k0 + cols.start, slope,
            masked=masked, window=window, positions=dropout_rate > 0.0)
        scored.append((s, mask, pos))
        m_new = _on_rows(jnp.maximum, m_new, rows,
                         jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l = l_scr[...] * alpha
    acc = acc_scr[...] * _lanes(alpha, acc_scr.shape[-1])
    for (rows, cols, _), (s, mask, pos) in zip(bands, scored):
        p = jnp.exp(s - _lanes(m_new[rows], s.shape[1]))
        if mask is not None and window is not None:
            # _NEG_INF is finite (-1e30): a row whose window lies entirely
            # outside this tile has s == m_new == -1e30 and exp(s - m_new)
            # would be 1, not 0 — zero masked entries explicitly.
            p = jnp.where(mask, p, 0.0)
        l = _on_rows(jnp.add, l, rows, jnp.sum(p, axis=-1, keepdims=True))
        if dropout_rate > 0.0:
            # l accumulates the *undropped* probabilities (dropout applies
            # after softmax normalization); only the V-contraction drops.
            keep = _keep_mask(*pos, seed, dropout_rate)
            p = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
        acc = _on_rows(jnp.add, acc, rows,
                       _dot(p.astype(v.dtype), v[cols], (1, 0)))
    m_scr[...] = m_new
    l_scr[...] = l
    acc_scr[...] = acc


def _on_rows(op, x, rows: slice, part):
    """``x`` with ``op(x[rows], part)`` in place of its ``rows`` (whole
    sublane groups: the concatenation moves nothing)."""
    if rows == slice(0, x.shape[0]):
        return op(x, part)
    return jnp.concatenate(
        [piece for piece in (x[:rows.start], op(x[rows], part),
                             x[rows.stop:]) if piece.shape[0]], axis=0)


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


def _fwd_result(m_scr, l_scr, acc_scr, dtype, ix):
    """``(out, logsumexp)`` of the walked tiles, the second in the form the
    layout stores it."""
    l = l_scr[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return ((acc_scr[...] / _lanes(l_safe, acc_scr.shape[-1])).astype(dtype),
            ix.lse_form(m_scr[...] + jnp.log(l_safe)))


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


_KEYS_OF_A_QUERY_TILE = (_EDGE, _DIAG)   # key_tile_ranges' two masked groups
_QUERIES_OF_A_KEY_TILE = (_DIAG, _EDGE)  # query_tile_ranges'


def _walk(ranges, body, edges):
    """``body(tile, edge)`` over the live tiles of one band row or column
    in ascending order, ``edges`` naming the edge of the masked tiles before
    and after the full ones; a walk whose bounds are statically empty (no
    window, no mask at all) is not traced."""
    lo, full_lo, full_hi, hi = ranges
    _loop(lo, full_lo, lambda i: body(i, edges[0]))
    _loop(full_lo, full_hi, lambda i: body(i, None))
    _loop(full_hi, hi, lambda i: body(i, edges[1]))


def _when_live(ranges, tile, step, causal: bool, edges=(_EDGE, _EDGE)):
    """The chunked kernels' form of :func:`_walk`: this grid step's ``tile``
    runs ``step(edge)`` if it is live, and nothing if not — one masked body
    where ``edges`` names the two groups of masked tiles alike (the kernels
    that do every masked tile whole)."""
    lo, full_lo, full_hi, hi = ranges
    within = lambda a, b: (tile >= a) & (tile < b)
    inside = within(full_lo, full_hi)
    pl.when(inside)(lambda: step(None))
    if not causal:
        return
    if edges[0] == edges[1]:
        pl.when(within(lo, hi) & ~inside)(lambda: step(edges[0]))
        return
    for (a, b), edge in zip(((lo, full_lo), (full_hi, hi)), edges):
        if not (isinstance(a, int) and isinstance(b, int) and a == b):
            pl.when(within(a, b))(functools.partial(step, edge))


def _tile(i, block: int, first: int = 0, count: int | None = None):
    """The rows of tile ``i``: all, or ``count`` of them from the ``first``."""
    count = block if count is None else count
    if isinstance(i, int):
        return pl.ds(i * block + first, count)
    return pl.ds(pl.multiple_of(i * block, block) + first, count)


_ALL = slice(None)


def _fwd_resident_kernel(seed_ref, alibi_ref, q_ref, k_ref, v_ref, o_ref,
                         lse_ref, m_scr, l_scr, acc_scr, *, ix,
                         causal: bool, sm_scale: float, block_q: int,
                         block_k: int, num_k: int, heads_per_step: int,
                         dropout_rate: float, window, use_alibi: bool,
                         grain: int):
    b, hs, qr = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tiles_per_step = ix.rows(q_ref) // block_q

    def head(hh):
        h = hs * heads_per_step + hh
        hkv = ix.kv_head(hh)
        slope, seed = _head_operands(seed_ref, alibi_ref, b, h, ix.heads,
                                     use_alibi, dropout_rate)

        def query_tile(qt):
            qi = qr * tiles_per_step + qt
            rows = _tile(qt, block_q)
            q = ix.scaled_q(q_ref, hh, rows, sm_scale)
            _fwd_init(m_scr, l_scr, acc_scr)

            def key_tile(kj, edge):
                cols = _tile(kj, block_k)
                _fwd_tile(q, ix.kv(k_ref, hkv, cols), ix.kv(v_ref, hkv, cols),
                          m_scr, l_scr, acc_scr, qi * block_q, kj * block_k,
                          slope, seed, edge=edge, grain=grain, window=window,
                          dropout_rate=dropout_rate)

            _walk(key_tile_ranges(qi, block_q, block_k, num_k, causal,
                                  window), key_tile, _KEYS_OF_A_QUERY_TILE)
            out, lse = _fwd_result(m_scr, l_scr, acc_scr, o_ref.dtype, ix)
            ix.put(o_ref, hh, rows, out)
            ix.put_lse(lse_ref, hh, rows, lse)

        _loop(0, tiles_per_step, query_tile)

    _loop(0, heads_per_step, head)


def _fwd_chunked_kernel(seed_ref, alibi_ref, q_ref, k_ref, v_ref, o_ref,
                        lse_ref, m_scr, l_scr, acc_scr, qs_scr, *,
                        ix, causal: bool, sm_scale: float,
                        block_q: int, block_k: int, num_k: int,
                        heads_per_step: int, dropout_rate: float, window,
                        use_alibi: bool, grain: int):
    b, hs, qi, kj = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                     pl.program_id(3))
    own = lambda ref, hh: _at(ref, hh, heads_per_step)

    def heads(body):
        _loop(0, heads_per_step, body)

    @pl.when(kj == 0)
    def _init():
        _fwd_init(m_scr, l_scr, acc_scr)

        def scale(hh):
            own(qs_scr, hh)[...] = ix.scaled_q(q_ref, hh, _ALL, sm_scale)

        heads(scale)

    def step(edge):
        def head(hh):
            hkv = ix.kv_head(hh)
            _fwd_tile(own(qs_scr, hh)[...], ix.kv(k_ref, hkv, _ALL),
                      ix.kv(v_ref, hkv, _ALL), own(m_scr, hh), own(l_scr, hh),
                      own(acc_scr, hh), qi * block_q, kj * block_k,
                      *_head_operands(seed_ref, alibi_ref, b,
                                      _head_index(hs, heads_per_step, hh),
                                      ix.heads, use_alibi, dropout_rate),
                      edge=edge, grain=grain, window=window,
                      dropout_rate=dropout_rate)

        heads(head)

    _when_live(key_tile_ranges(qi, block_q, block_k, num_k, causal, window),
               kj, step, causal, _KEYS_OF_A_QUERY_TILE)

    @pl.when(kj == num_k - 1)
    def _finish():
        def head(hh):
            out, lse = _fwd_result(own(m_scr, hh), own(l_scr, hh),
                                   own(acc_scr, hh), o_ref.dtype, ix)
            ix.put(o_ref, hh, _ALL, out)
            ix.put_lse(lse_ref, hh, _ALL, lse)

        heads(head)


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


def _bhtd(q, k, v) -> _HeadMajor:
    Dv = v.shape[-1]
    return _HeadMajor(D=q.shape[-1], heads=q.shape[1], kv_heads=k.shape[1],
                      Dv=None if Dv == q.shape[-1] else Dv)


def _flash_forward(q, k, v, causal: bool = True,
                   block_q: int | None = None, block_k: int | None = None,
                   dropout_rate: float = 0.0, seed=None,
                   interpret: bool = False, return_lse: bool = False,
                   window=None, alibi=None, scale=None,
                   plan: FlashPlan | None = None, ix=None):
    """The forward call.  ``ix`` None: q ``(B, Hq, T, D)``, k/v ``(B, Hkv,
    S, D)``; a ``btd`` indexer: ``(B, T, ·)`` arrays (module docstring)."""
    ix = ix or _bhtd(q, k, v)
    B, T, S = ix.dims(q, k)
    D, Hq = ix.D, ix.heads
    Dv = ix.vwidth // ix.hpb
    if plan is None:
        plan = plan_flash(T, S, D, q.dtype.itemsize, causal, window,
                          heads=Hq, group=ix.group, block_q=block_q,
                          block_k=block_k,
                          layout=ix.layout, Dv=Dv)
    block_q, block_k = plan.block_q, plan.block_k
    sm_scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    num_k = S // block_k
    seed, alibi_arr = _smem_operands(seed, alibi)
    hps = plan.heads_per_step if plan.resident else ix.hpb
    common = dict(ix=ix, causal=causal, sm_scale=sm_scale, block_q=block_q,
                  block_k=block_k, num_k=num_k, heads_per_step=hps,
                  dropout_rate=dropout_rate, window=window,
                  use_alibi=alibi is not None, grain=plan.diag_grain)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    stats = [(block_q, _LANES), (block_q, _LANES), (block_q, ix.vwidth)]
    if plan.resident:
        kernel = functools.partial(_fwd_resident_kernel, **common)
        grid = (B, Hq // hps, T // plan.q_rows)
        at = lambda b, h, i: (b, h, i)
        kv_at = lambda b, h, i: (b, h, 0)
        q_rows, kv_rows = plan.q_rows, S
        scratch = [pltpu.VMEM(shape, jnp.float32) for shape in stats]
        semantics = ("parallel", "parallel", "parallel")
    else:
        kernel = functools.partial(_fwd_chunked_kernel, **common)
        grid = (B, Hq // hps, T // block_q, num_k)
        clamp = _clamped(key_tile_ranges, block_q, block_k, num_k, causal,
                         window)
        at = lambda b, h, i, j: (b, h, i)
        kv_at = lambda b, h, i, j: (b, h, clamp(i, j))
        q_rows, kv_rows = block_q, block_k
        scratch = ([pltpu.VMEM(_per_head(hps, shape), jnp.float32)
                    for shape in stats]
                   + [pltpu.VMEM(_per_head(hps, (block_q, ix.width)),
                                 q.dtype)])
        semantics = ("parallel", "parallel", "parallel", "arbitrary")
    q_spec = ix.spec(hps, q_rows, at, 0)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[smem, smem, q_spec, ix.kv_spec(hps, kv_rows, kv_at, 1),
                  ix.kv_spec(hps, kv_rows, kv_at, 2)],
        out_specs=[ix.vspec(hps, q_rows, at),
                   ix.stat_spec(hps, q_rows, at, as_rows=False)],
        out_shape=[
            jax.ShapeDtypeStruct(ix.like_v(B, T), q.dtype),
            jax.ShapeDtypeStruct(ix.lse_shape(B, T), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * (D + Dv) * B * Hq * T * S * _live_share(causal)),
            bytes_accessed=int((B * Hq * T + B * ix.kv_heads * S)
                               * (D + Dv) * q.dtype.itemsize),
            transcendentals=int(B * Hq * T * S * _live_share(causal))),
        interpret=interpret,
        name="penroz_flash_fwd",
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
                      qs_scr, dq_scr, stat_scr, dk_scr, dv_scr, *, ix,
                      causal: bool, sm_scale: float, block_q: int,
                      block_k: int, heads_per_step: int,
                      dropout_rate: float, window, use_alibi: bool,
                      grain: int):
    """One pass over the live tiles of ``heads_per_step`` heads, key tiles
    outermost, on transposed (block_k, block_q) tiles: dV and dK of a key
    tile accumulate in scratch over its query tiles, dQ of the whole head
    in a (T, D) f32 scratch that is written once.  Where a lane block holds
    two heads the scratch is the block's width and only the head's own
    lanes of it are kept (:meth:`_LaneBlocks.put`)."""
    b, hs = pl.program_id(0), pl.program_id(1)
    num_q = ix.rows(q_ref) // block_q
    num_k = ix.rows(k_ref) // block_k

    def head(hh):
        h = hs * heads_per_step + hh
        hkv = ix.kv_head(hh)
        slope, seed = _head_operands(seed_ref, alibi_ref, b, h, ix.heads,
                                     use_alibi, dropout_rate)
        qs_scr[...] = ix.scaled_q(q_ref, hh, _ALL, sm_scale)
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # the head's logsumexp and δ rows, side by side: a band reads a
        # 128-lane share of each, which Mosaic loads from a static sublane
        # only (``ix.row`` finds a head at a dynamic one)
        stat_scr[0:1, :] = ix.row(lse_ref, hh, _ALL)
        stat_scr[1:2, :] = ix.row(delta_ref, hh, _ALL)

        def key_tile(kj):
            cols = _tile(kj, block_k)
            k = ix.kv(k_ref, hkv, cols)
            v = ix.own(ix.kv(v_ref, hkv, cols), hh)
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

            def query_tile(qi, edge):
                def part(keys, queries, masked):
                    """Rows ``keys`` of the tile's keys against ``queries``
                    of its queries (slices of the tile)."""
                    rows = _tile(qi, block_q, queries.start,
                                 queries.stop - queries.start)
                    q = qs_scr[rows, :]
                    do = ix.get(do_ref, hh, rows)
                    p, drop_scale = _recompute_probs(
                        q, k[keys], stat_scr[0:1, rows],
                        qi * block_q + queries.start,
                        kj * block_k + keys.start, slope, seed,
                        masked=masked, window=window,
                        dropout_rate=dropout_rate, transposed=True)
                    dp = _dot(v[keys], do, (1, 1))            # (dO·Vᵀ)ᵀ
                    if drop_scale is not None:
                        dp = dp * drop_scale
                        p_drop = p * drop_scale
                    else:
                        p_drop = p
                    dv_scr[keys, :] += _dot(p_drop.astype(do.dtype), do,
                                            (1, 0))
                    ds = (p * (dp - stat_scr[1:2, rows])).astype(q.dtype)
                    dk_scr[keys, :] += _dot(ds, q, (1, 0))  # q holds sm_scale
                    dq_scr[rows, :] += _dot(ds, k[keys], (0, 0))  # scaled last

                if not _cuts_diagonal(edge, grain, block_q, block_k):
                    return part(slice(0, block_k), slice(0, block_q),
                                edge is not None)
                # the tile on the diagonal: each band of its keys against
                # the queries from the diagonal down (bands of queries, and
                # a mask on the diagonal's sub-block alone, were slower:
                # CHANGES.md PR 38)
                for c, hi in _diag_bands(block_k, grain, window):
                    part(_grains(c, c + 1, grain), _grains(c, hi, grain),
                         True)

            _walk(query_tile_ranges(kj, block_q, block_k, num_q, causal,
                                    window), query_tile,
                  _QUERIES_OF_A_KEY_TILE)
            ix.put(dk_ref, hh, cols, dk_scr[...].astype(dk_ref.dtype))
            ix.put(dv_ref, hh, cols, dv_scr[...].astype(dv_ref.dtype))

        _loop(0, num_k, key_tile)
        ix.put(dq_ref, hh, _ALL, (dq_scr[...] * sm_scale).astype(dq_ref.dtype))

    _loop(0, heads_per_step, head)


def _dq_kernel(seed_ref, alibi_ref, q_ref, k_ref, v_ref, lse_ref, delta_ref,
               do_ref, dq_ref, dq_scr, qs_scr, *, ix, causal: bool,
               sm_scale: float, block_q: int, block_k: int, num_k: int,
               heads_per_step: int, dropout_rate: float, window,
               use_alibi: bool):
    """dQ of one query tile, K/V streaming.  Tiles are (block_q, block_k)
    against logsumexp / δ columns, or transposed against rows where the
    layout stores them so (``ix.stat_rows``)."""
    b, hs, qi, kj = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                     pl.program_id(3))
    own = lambda ref, hh: _at(ref, hh, heads_per_step)
    t = ix.stat_rows

    def heads(body):
        _loop(0, heads_per_step, body)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

        def scale(hh):
            own(qs_scr, hh)[...] = ix.scaled_q(q_ref, hh, _ALL, sm_scale)

        heads(scale)

    def step(edge):
        def head(hh):
            hkv = ix.kv_head(hh)
            k = ix.kv(k_ref, hkv, _ALL)
            p, drop_scale = _recompute_probs(
                own(qs_scr, hh)[...], k, ix.stat(lse_ref, hh),
                qi * block_q, kj * block_k,
                *_head_operands(seed_ref, alibi_ref, b,
                                _head_index(hs, heads_per_step, hh),
                                ix.heads, use_alibi, dropout_rate),
                masked=edge is not None, window=window,
                dropout_rate=dropout_rate, transposed=t)
            do = ix.get(do_ref, hh, _ALL)
            v = ix.own(ix.kv(v_ref, hkv, _ALL), hh)
            dp = _dot(v, do, (1, 1)) if t else _dot(do, v, (1, 1))
            if drop_scale is not None:
                dp = dp * drop_scale
            ds = p * (dp - ix.stat(delta_ref, hh))
            own(dq_scr, hh)[...] += _dot(ds.astype(k.dtype), k,
                                         (0, 0) if t else (1, 0))

        heads(head)

    _when_live(key_tile_ranges(qi, block_q, block_k, num_k, causal, window),
               kj, step, causal)

    @pl.when(kj == num_k - 1)
    def _finish():
        heads(lambda hh: ix.put(
            dq_ref, hh, _ALL,
            (own(dq_scr, hh)[...] * sm_scale).astype(dq_ref.dtype)))


def _dkv_kernel(seed_ref, alibi_ref, q_ref, k_ref, v_ref, lse_ref,
                delta_ref, do_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                ix, causal: bool, sm_scale: float, block_q: int,
                block_k: int, num_q: int, heads_per_step: int,
                dropout_rate: float, window, use_alibi: bool):
    """dK, dV of one key tile, Q/dO streaming; tiles as in :func:`_dq_kernel`."""
    b, hs, kj, qi = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                     pl.program_id(3))
    own = lambda ref, hh: _at(ref, hh, heads_per_step)
    t = ix.stat_rows

    def heads(body):
        _loop(0, heads_per_step, body)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(edge):
        def head(hh):
            q = ix.scaled_q(q_ref, hh, _ALL, sm_scale)
            do = ix.get(do_ref, hh, _ALL)
            hkv = ix.kv_head(hh)
            p, drop_scale = _recompute_probs(
                q, ix.kv(k_ref, hkv, _ALL), ix.stat(lse_ref, hh),
                qi * block_q, kj * block_k,
                *_head_operands(seed_ref, alibi_ref, b,
                                _head_index(hs, heads_per_step, hh),
                                ix.heads, use_alibi, dropout_rate),
                masked=edge is not None, window=window,
                dropout_rate=dropout_rate, transposed=t)
            p_drop = p if drop_scale is None else p * drop_scale
            own(dv_scr, hh)[...] += _dot(p_drop.astype(do.dtype), do,
                                         (1, 0) if t else (0, 0))  # p̃ᵀ·dO
            v = ix.own(ix.kv(v_ref, hkv, _ALL), hh)
            dp = _dot(v, do, (1, 1)) if t else _dot(do, v, (1, 1))
            if drop_scale is not None:
                dp = dp * drop_scale
            ds = p * (dp - ix.stat(delta_ref, hh))
            own(dk_scr, hh)[...] += _dot(ds.astype(q.dtype), q,
                                         (1, 0) if t else (0, 0))  # dSᵀ·Q

        heads(head)

    _when_live(query_tile_ranges(kj, block_q, block_k, num_q, causal,
                                 window), qi, step, causal)

    @pl.when(qi == num_q - 1)
    def _finish():
        def head(hh):
            ix.put(dk_ref, hh, _ALL, own(dk_scr, hh)[...].astype(dk_ref.dtype))
            ix.put(dv_ref, hh, _ALL, own(dv_scr, hh)[...].astype(dv_ref.dtype))

        heads(head)


def _delta_kernel(o_ref, do_ref, delta_ref, *, ix, block_q: int):
    """δ rows of one lane block: the sums over each head's lanes as one
    matmul with a 0/1 matrix (row ``hh`` picks head ``hh``'s lanes), which
    lands them lane-dense."""
    shape = (8, ix.width)
    head = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    pick = jnp.where((lane >= head * ix.D) & (lane < (head + 1) * ix.D),
                     1.0, 0.0)

    def tile(i):
        rows = _tile(i, block_q)
        prod = (do_ref[0, rows, :].astype(jnp.float32)
                * o_ref[0, rows, :].astype(jnp.float32))
        delta_ref[0, 0, :, rows] = _dot(pick, prod, (1, 1))[:ix.hpb]

    _loop(0, ix.rows(o_ref) // block_q, tile)


def _flash_backward(q, k, v, out, lse, g, causal: bool, plan: FlashPlan,
                    dropout_rate: float, seed, interpret: bool = False,
                    window=None, alibi=None, scale=None, ix=None):
    """``(dq, dk, dv)`` shaped like q, k, v (of their own arrays in the
    ``btd`` layout, whatever array the forward read them from)."""
    ix = ix or _bhtd(q, k, v)
    B, T, S = ix.dims(q, k)
    D, Hq, Hkv, group = ix.D, ix.heads, ix.kv_heads, ix.group
    Dv = ix.vwidth // ix.hpb
    block_q, block_k = plan.bwd_block_q, plan.bwd_block_k
    sm_scale = float(scale) if scale is not None else 1.0 / (D ** 0.5)
    num_q, num_k = T // block_q, S // block_k
    seed, alibi_arr = _smem_operands(seed, alibi)
    hps = plan.heads_per_step if plan.fused_bwd else ix.hpb
    itemsize = q.dtype.itemsize

    delta = ix.delta(out, g, block_q, interpret)
    if plan.fused_bwd:
        lse, delta = ix.as_rows(lse), ix.as_rows(delta)
    common = dict(ix=ix, causal=causal, sm_scale=sm_scale, block_q=block_q,
                  block_k=block_k, heads_per_step=hps,
                  dropout_rate=dropout_rate, window=window,
                  use_alibi=alibi is not None)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    flops = int(2 * (3 * D + 2 * Dv) * B * Hq * T * S * _live_share(causal))
    exps = int(B * Hq * T * S * _live_share(causal))
    # q, dO and dq; k and v; the per-query-head dk and dv
    q_bytes = B * Hq * T * (2 * D + Dv) * itemsize
    kv_bytes = B * Hkv * S * (D + Dv) * itemsize
    dkv_bytes = B * Hq * S * (D + Dv) * itemsize
    dq_shape = jax.ShapeDtypeStruct(ix.like_q(B, T), q.dtype)
    dkv_shape = [jax.ShapeDtypeStruct(ix.like_q(B, S), k.dtype),
                 jax.ShapeDtypeStruct(ix.like_v(B, S), v.dtype)]
    width, vwidth = ix.width, ix.vwidth

    if plan.fused_bwd:
        at = lambda b, h: (b, h, 0)
        q_spec = ix.spec(hps, T, at, 0)
        own_spec = ix.spec(hps, T, at)
        row_spec = ix.stat_spec(hps, T, at, as_rows=True)
        dq, dk_ph, dv_ph = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, grain=plan.bwd_diag_grain,
                              **common),
            grid=(B, Hq // hps),
            in_specs=[smem, smem, q_spec, ix.kv_spec(hps, S, at, 1),
                      ix.kv_spec(hps, S, at, 2), row_spec, row_spec,
                      ix.vspec(hps, T, at)],
            out_specs=[own_spec, ix.spec(hps, S, at), ix.vspec(hps, S, at)],
            out_shape=[dq_shape] + dkv_shape,
            scratch_shapes=[pltpu.VMEM((T, width), q.dtype),
                            pltpu.VMEM((T, width), jnp.float32),
                            pltpu.VMEM((2, T), jnp.float32),
                            pltpu.VMEM((block_k, width), jnp.float32),
                            pltpu.VMEM((block_k, vwidth), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                # a plan past the compiler's default share says so; one
                # within it compiles to the program it always was
                vmem_limit_bytes=(_asked(plan.bwd_vmem_bytes)
                                  if plan.bwd_vmem_bytes > VMEM_BUDGET
                                  else None)),
            cost_estimate=pl.CostEstimate(
                flops=flops, transcendentals=exps,
                bytes_accessed=q_bytes + kv_bytes + dkv_bytes),
            interpret=interpret,
            name="penroz_flash_bwd",
        )(seed, alibi_arr, q, k, v, lse, delta, g)
    else:
        clamp_k = _clamped(key_tile_ranges, block_q, block_k, num_k, causal,
                           window)
        at = lambda b, h, i, j: (b, h, i)
        kv_at = lambda b, h, i, j: (b, h, clamp_k(i, j))
        own_spec = ix.spec(hps, block_q, at)
        stat_spec = ix.stat_spec(hps, block_q, at, as_rows=False)
        semantics = pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary"))
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, num_k=num_k, **common),
            grid=(B, Hq // hps, num_q, num_k),
            in_specs=[smem, smem, ix.spec(hps, block_q, at, 0),
                      ix.kv_spec(hps, block_k, kv_at, 1),
                      ix.kv_spec(hps, block_k, kv_at, 2), stat_spec,
                      stat_spec, ix.vspec(hps, block_q, at)],
            out_specs=own_spec,
            out_shape=dq_shape,
            scratch_shapes=[pltpu.VMEM(_per_head(hps, (block_q, width)),
                                       jnp.float32),
                            pltpu.VMEM(_per_head(hps, (block_q, width)),
                                       q.dtype)],
            compiler_params=semantics,
            cost_estimate=pl.CostEstimate(
                flops=flops // 2, transcendentals=exps,
                bytes_accessed=q_bytes + kv_bytes),
            interpret=interpret,
            name="penroz_flash_bwd_dq",
        )(seed, alibi_arr, q, k, v, lse, delta, g)

        # K/V-resident kernel: Q, dO, lse, δ stream through the inner grid.
        # index maps take (b, h, kj, qi) — q-row specs select on qi (dim 3).
        clamp_q = _clamped(query_tile_ranges, block_q, block_k, num_q,
                           causal, window)
        q_at = lambda b, h, j, i: (b, h, clamp_q(j, i))
        kv_at = lambda b, h, j, i: (b, h, j)
        stream = ix.vspec(hps, block_q, q_at)
        stat_stream = ix.stat_spec(hps, block_q, q_at, as_rows=False)
        dkv_scr = [pltpu.VMEM(_per_head(hps, (block_k, w)), jnp.float32)
                   for w in (width, vwidth)]
        dk_ph, dv_ph = pl.pallas_call(
            functools.partial(_dkv_kernel, num_q=num_q, **common),
            grid=(B, Hq // hps, num_k, num_q),
            in_specs=[smem, smem, ix.spec(hps, block_q, q_at, 0),
                      ix.kv_spec(hps, block_k, kv_at, 1),
                      ix.kv_spec(hps, block_k, kv_at, 2), stat_stream,
                      stat_stream, stream],
            out_specs=[ix.spec(hps, block_k, kv_at),
                       ix.vspec(hps, block_k, kv_at)],
            out_shape=dkv_shape,
            scratch_shapes=dkv_scr,
            compiler_params=semantics,
            cost_estimate=pl.CostEstimate(
                flops=flops // 2, transcendentals=exps,
                bytes_accessed=q_bytes + 2 * dkv_bytes),
            interpret=interpret,
            name="penroz_flash_bwd_dkv",
        )(seed, alibi_arr, q, k, v, lse, delta, g)

    if group > 1:
        dk = ix.sum_groups(dk_ph).astype(k.dtype)
        dv = ix.sum_groups(dv_ph).astype(v.dtype)
    else:
        dk = dk_ph.astype(k.dtype)
        dv = dv_ph.astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP
# ---------------------------------------------------------------------------


def _qkv_of(arrays):
    """(q, k, v) as the calls take them: three arrays, or the one fused
    projection three times (the indexer's offsets tell the ranges apart)."""
    return arrays if len(arrays) == 3 else arrays * 3


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(2, 10)))
def _flash(arrays, seed, ix, causal, plan, dropout_rate, interpret, window,
           alibi, scale):
    return _flash_forward(*_qkv_of(arrays), causal,
                          dropout_rate=dropout_rate, seed=seed,
                          interpret=interpret, window=window, alibi=alibi,
                          scale=scale, plan=plan, ix=ix)


def _flash_fwd_rule(arrays, seed, ix, causal, plan, dropout_rate, interpret,
                    window, alibi, scale):
    out, lse = _flash_forward(*_qkv_of(arrays), causal,
                              dropout_rate=dropout_rate, seed=seed,
                              interpret=interpret, return_lse=True,
                              window=window, alibi=alibi, scale=scale,
                              plan=plan, ix=ix)
    out, lse = checkpoint_name(out, OUT_NAME), checkpoint_name(lse, LSE_NAME)
    return out, (arrays, seed, out, lse)


def _flash_bwd_rule(ix, causal, plan, dropout_rate, interpret, window, alibi,
                    scale, residuals, g):
    arrays, seed, out, lse = residuals
    grads = _flash_backward(*_qkv_of(arrays), out, lse, g, causal, plan,
                            dropout_rate, seed, interpret=interpret,
                            window=window, alibi=alibi, scale=scale, ix=ix)
    if len(arrays) == 1:
        # the fused projection's cotangent, its three ranges side by side
        grads = (jnp.concatenate(grads, axis=-1),)
    return grads, np.zeros((), dtype=jax.dtypes.float0)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _static_args(seed, alibi, heads: int, window, scale):
    if seed is None:
        seed = jnp.zeros((), jnp.int32)
    if alibi is not None:
        # static tuple: slopes are a pure function of the head count, so
        # baking them into the trace costs nothing and keeps the
        # custom_vjp arity fixed
        alibi = tuple(float(a) for a in np.asarray(alibi).reshape(-1))
        if len(alibi) != heads:
            raise ValueError(f"alibi needs one slope per query head "
                             f"({heads}), got {len(alibi)}")
    return (jnp.asarray(seed, jnp.int32),
            int(window) if window is not None else None, alibi,
            float(scale) if scale is not None else None)


def flash_attention(q, k, v, causal: bool = True,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    dropout_rate: float = 0.0, seed=None,
                    interpret: bool = False, window=None, alibi=None,
                    scale=None, vmem_budget: int = VMEM_BUDGET):
    """Flash attention with a fused flash backward.

    q: (B, Hq, T, D); k: (B, Hkv, S, D); v: (B, Hkv, S, Dv) with
    Hq % Hkv == 0; the result is (B, Hq, T, Dv).  ``Dv`` is ``D`` in most
    models and narrower under latent attention (192-wide scores over
    128-wide values).
    ``dropout_rate`` > 0 applies post-softmax dropout inside the kernels
    (mask derived from ``seed`` — pass a fresh int32 scalar per step).
    ``window``: sliding-window width (causal only) — query t attends keys
    in ``(t - window, t]``; off-band tiles are never visited.

    Tile sizes, K/V residency, the backward's form and the heads a grid
    step owns come from :func:`plan_flash` on the shapes; ``block_q`` /
    ``block_k`` override the tile sizes and ``vmem_budget`` the bytes the
    plan may count on (a small one forces the chunked and split kernels).
    """
    B, Hq, T, D = q.shape
    Hkv, S, Dv = k.shape[1], k.shape[2], v.shape[-1]
    seed, window, alibi, scale = _static_args(seed, alibi, Hq, window, scale)
    plan = plan_flash(T, S, D, q.dtype.itemsize, bool(causal), window,
                      heads=Hq, group=Hq // Hkv,
                      block_q=int(block_q) if block_q else None,
                      block_k=int(block_k) if block_k else None,
                      vmem_budget=int(vmem_budget), Dv=Dv)
    _record_plan(T, S, D, plan, Dv)
    return _flash((q, k, v), seed, _bhtd(q, k, v), bool(causal), plan,
                  float(dropout_rate), bool(interpret), window, alibi, scale)


def btd_refusal(D: int, heads: int, kv_heads: int,
                Dv: int | None = None) -> str | None:
    """Why :func:`flash_attention_btd` cannot take ``heads`` query heads on
    ``kv_heads`` K/V heads of size ``D`` (values ``Dv`` wide, default the
    same), or None if it can."""
    if Dv not in (None, D):
        return (f"scores {D} wide over values {Dv} wide, (D, Dv)=({D}, "
                f"{Dv}): a head's lanes start off the 128-lane blocks, the "
                f"(B, H, T, ·) kernels take the pair")
    if D not in (64, 128, 256):
        return f"head size {D} fills no whole 128-lane block"
    hpb = _heads_per_block(D)
    if heads % hpb or kv_heads % hpb:
        return (f"{heads} query / {kv_heads} K/V heads of size {D} do not "
                f"fill whole 128-lane blocks ({hpb} heads a block)")
    if hpb > 1 and heads != kv_heads:
        return (f"grouped-query attention at head size {D}: a lane block's "
                f"{hpb} query heads need their K/V heads at the same lanes")
    return None


def flash_attention_btd(q, k=None, v=None, *, heads: int,
                        kv_heads: int | None = None, causal: bool = True,
                        block_q: int | None = None,
                        block_k: int | None = None,
                        dropout_rate: float = 0.0, seed=None,
                        interpret: bool = False, window=None, alibi=None,
                        scale=None, vmem_budget: int = VMEM_BUDGET):
    """:func:`flash_attention` in the model's own layout (module docstring).

    ``q`` alone: the fused projection ``(B, T, (heads + 2·kv_heads)·D)``,
    read in place — q, k, v are its lane ranges, and its cotangent comes
    back whole.  With ``k`` and ``v``: q ``(B, T, heads·D)``, k/v ``(B, S,
    kv_heads·D)``.  Returns ``(B, T, heads·D)``.  Everything else as
    :func:`flash_attention`; :func:`btd_refusal` names the head shapes this
    entry does not take."""
    kv_heads = heads if kv_heads is None else kv_heads
    fused = k is None
    if fused != (v is None):
        raise ValueError("pass the fused projection alone, or q, k and v")
    B, T, width = q.shape
    S = T if fused else k.shape[1]
    D = width // (heads + 2 * kv_heads if fused else heads)
    refusal = btd_refusal(D, heads, kv_heads)
    if refusal:
        raise ValueError(f"flash_attention_btd: {refusal}")
    seed, window, alibi, scale = _static_args(seed, alibi, heads, window,
                                              scale)
    plan = plan_flash(T, S, D, q.dtype.itemsize, bool(causal), window,
                      heads=heads, group=heads // kv_heads,
                      block_q=int(block_q) if block_q else None,
                      block_k=int(block_k) if block_k else None,
                      vmem_budget=int(vmem_budget), layout="btd",
                      fused_qkv=fused)
    _record_plan(T, S, D, plan)
    ix = _LaneBlocks(D=D, heads=heads, kv_heads=kv_heads, fused=fused)
    return _flash((q,) if fused else (q, k, v), seed, ix, bool(causal), plan,
                  float(dropout_rate), bool(interpret), window, alibi, scale)
