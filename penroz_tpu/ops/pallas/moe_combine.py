"""Pallas TPU kernel that brings the dropless expert layer's rows back to
their tokens by reads.

``ops/modules.py::MixtureOfExperts`` with ``dispatch="dropless"`` lays the
(token, choice) pairs of the experts it holds out sorted by expert, and the
sort is stable: **inside an expert's group the rows ascend in their token**,
so the rows of any range of tokens are one contiguous run of each group.
:func:`rows_to_tokens` leans on that.  A grid step owns a tile of tokens,
kept in VMEM; for each held expert it copies the run of rows that belongs to
the tile from HBM (whole chunks of ``chunk`` rows, every copy of a tile in
flight at once) and adds each row, scaled, to its token's line of the tile:

    ``y[n] = y_in[n] + Σ_{rows r of token n} scale[r] · rows[r]``  (float32)

No row is written anywhere but into the resident tile: where XLA's
scatter-add of the same rows walks them one read-modify-write at a time
through HBM (1.1–1.5 ms for 4 096–8 192 rows of 3 072 on a v5e, PERF.md §5),
this reads each row once.  Which rows belong to a tile comes from the layout
(``run_lo``, ``run_hi``: the run's first row and one past its last, per
expert and token tile), the rows' tokens and scales ride along as scalars.

Off the TPU, and where :func:`fits` says the shapes are not the kernel's,
``ops/modules.py::_rows_to_tokens`` computes the same sum by reads too
(``jnp.take`` of the row a token has in each of its places), which is what
the CPU tests run and what the kernel is checked against in interpret mode
(tests/test_moe.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TOKEN_TILE = 128        # tokens a grid step keeps resident
_CHUNK = 16             # rows a copy brings in (a packed bf16 tile's rows)
_VMEM_LIMIT = 48 * 1024 * 1024


def _combine_kernel(lo_ref, hi_ref, tok_ref, scale_ref, rows_ref, y_in_ref,
                    y_ref, buf, sems, *, groups: int, chunk: int):
    i = pl.program_id(0)
    tiles = pl.num_programs(0)
    first_token = i * y_ref.shape[0]
    y_ref[...] = y_in_ref[...]

    def chunks(e):
        lo, hi = lo_ref[e * tiles + i], hi_ref[e * tiles + i]
        # no chunk at all for an empty run
        return lo, hi, lo // chunk, jnp.where(hi > lo, -(-hi // chunk),
                                              lo // chunk)

    def copy(c, slot):
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(pl.multiple_of(c * chunk, chunk), chunk), :],
            buf.at[slot], sems.at[slot])

    def start_group(e, slot):
        _, _, c0, c1 = chunks(e)

        def start(c, slot):
            copy(c, slot).start()
            return slot + 1

        return jax.lax.fori_loop(c0, c1, start, slot)

    jax.lax.fori_loop(0, groups, start_group, jnp.int32(0))

    def add_group(e, slot):
        lo, hi, c0, c1 = chunks(e)

        def add(c, slot):
            copy(c, slot).wait()
            got = buf[slot].astype(jnp.float32)
            for r in range(chunk):
                row = c * chunk + r

                @pl.when((row >= lo) & (row < hi))
                def _():
                    line = pl.ds(tok_ref[row] - first_token, 1)
                    y_ref[line, :] += got[r:r + 1, :] * scale_ref[row]

            return slot + 1

        return jax.lax.fori_loop(c0, c1, add, slot)

    jax.lax.fori_loop(0, groups, add_group, jnp.int32(0))


def token_tile(tokens: int) -> int:
    """Tokens a grid step owns: ``TOKEN_TILE`` where it divides, else all."""
    return TOKEN_TILE if tokens % TOKEN_TILE == 0 else tokens


def _slots(tile: int, places: int, groups: int, chunk: int) -> int:
    """Copies a grid step can have in flight: every token of the tile in
    every place, and a chunk each way of every run's ends."""
    return -(-tile * places // chunk) + 2 * groups


def fits(*, rows: int, tokens: int, width: int, groups: int,
         places: int) -> bool:
    """Whether the kernel takes these shapes on a TPU: whole token tiles of
    lane-aligned width, its scalars (a token and a scale a row, two run
    ends an expert and token tile) within three quarters of the core's
    1 MiB of SMEM, a DMA semaphore a copy in flight within its 512, and
    the copies' landing buffer (reckoned for float32 rows) beside the
    tile's in and out blocks within the VMEM the call asks for.  (Compiled
    for a described v5e: 32 768 rows of 3 072 pass, 131 072 rows run out of
    SMEM and 256 experts out of semaphores.)"""
    if tokens % TOKEN_TILE or width % 128 or rows % _CHUNK:
        return False
    slots = _slots(TOKEN_TILE, places, groups, _CHUNK)
    smem = 8 * rows + 8 * groups * (tokens // TOKEN_TILE)
    vmem = width * 4 * (slots * _CHUNK + 4 * TOKEN_TILE)
    return (smem <= 768 * 1024 and slots <= 448
            and vmem <= _VMEM_LIMIT - 8 * 1024 * 1024)


def rows_to_tokens(rows, scale, row_token, run_lo, run_hi, y_in, *,
                   places: int, interpret: bool = False):
    """``y_in[n] + Σ_{r: row_token[r] = n} scale[r] · rows[r]`` over the rows
    the runs name, float32 ``(tokens, d)`` (``y_in`` is given up to it).

    ``rows`` ``(R, d)``; ``scale`` ``(R,)`` float32 and ``row_token`` ``(R,)``
    int32 are read only at the rows of a run.  ``run_lo``, ``run_hi``
    ``(groups, tokens // token_tile(tokens))`` int32: group ``e``'s rows whose
    token lies in tile ``i`` are ``[run_lo[e, i], run_hi[e, i])``, ascending
    in their token and unique in it.  ``places``: the most rows one token can
    have (what the resident copies are sized for)."""
    r, d = rows.shape
    tokens = y_in.shape[0]
    tn = token_tile(tokens)
    groups, tiles = run_lo.shape
    if tiles != tokens // tn:
        raise ValueError(f"{tiles} run tiles for {tokens} tokens in tiles "
                         f"of {tn}")
    chunk = math.gcd(r, _CHUNK)
    slots = _slots(tn, places, groups, chunk)
    return pl.pallas_call(
        functools.partial(_combine_kernel, groups=groups, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((tn, d), lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec((tn, d), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((slots, chunk, d), rows.dtype),
                            pltpu.SemaphoreType.DMA((slots,))]),
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="penroz_moe_combine",
    )(run_lo.reshape(-1).astype(jnp.int32),
      run_hi.reshape(-1).astype(jnp.int32),
      row_token.astype(jnp.int32), scale.astype(jnp.float32), rows,
      y_in.astype(jnp.float32))
