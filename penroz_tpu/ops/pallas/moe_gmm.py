"""Pallas TPU grouped matrix products for the dropless expert layer.

``ops/modules.py::MixtureOfExperts`` with ``dispatch="dropless"`` sorts the
(token, choice) pairs whose expert it holds into a row buffer, every expert's
group padded to ``row_tile`` rows, so that **a tile of rows belongs to one
expert**.  ``tile_group`` says which: one int32 a tile, the held expert's
index, or ``groups`` (one past the last) for a tile that holds no row at all
(the buffer's static size is a bound; what was really routed ends earlier).
It is the kernels' scalar prefetch: the index maps read it to bring in the
right expert's weights, and the bodies skip the empty tiles, so the time a
call takes follows the rows really routed and not the buffer's size.

Three calls, named so that a trace and a reader find them:

- ``penroz_moe_gmm_fwd``     ``out[t] = lhs[t] · rhs[g(t)]ᵀ``  (weights are
  stored ``(out, in)``, as every linear weight of the program)
- ``penroz_moe_gmm_bwd_dx``  ``dlhs[t] = dout[t] · rhs[g(t)]``  (the same
  body, the weights taken as they lie)
- ``penroz_moe_gmm_bwd_dw``  ``drhs[g] = Σ_{t: g(t) = g} dout[t]ᵀ · lhs[t]``

bf16 (or f32) operands, float32 accumulation.  Grid of the first two:
(column tiles, row tiles), rows innermost, the whole contraction in one
block: consecutive row tiles of one expert find its weight block already in
VMEM (Pallas brings a block in only when its index changes).  The third walks
the row tiles innermost too and accumulates an expert's ``(out tile, in
tile)`` block in VMEM scratch from its first tile to its last.  An expert
that got no row is never visited: its block is zeroed afterwards.

Off the TPU :func:`grouped_matmul` is ``jax.lax.ragged_dot`` over the same
buffer (the groups' padded sizes), which is what the CPU tests run and what
the kernels are checked against in interpret mode (tests/test_moe.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_ROW_TILE = 128
_COL_TILE = 512         # columns of the result a grid step computes
_DW_IN_TILE = 1024      # the weight gradient's (out tile, in tile) block


def _dot_precision(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_dot_precision(a.dtype))


def _tile(n: int, want: int) -> int:
    """``want`` where it divides ``n``; else the largest multiple of the 128
    lanes under it that does (2688 = 21 · 128 takes 384: the whole width as
    one block is past the core's VMEM beside a 1024-long contraction); all
    of ``n`` where none does (shapes off the lanes: the tests')."""
    if n % want == 0:
        return want
    return next((t for t in range(want - want % 128, 0, -128) if n % t == 0),
                n)


def _gmm_kernel(group_ref, lhs_ref, rhs_ref, out_ref, *, groups: int,
                transpose_rhs: bool):
    t = pl.program_id(1)

    @pl.when(group_ref[t] < groups)
    def _compute():
        out_ref[...] = _dot(lhs_ref[...], rhs_ref[...],
                            (1, 1) if transpose_rhs else (1, 0)
                            ).astype(out_ref.dtype)

    @pl.when(group_ref[t] >= groups)
    def _empty():
        out_ref[...] = jnp.zeros_like(out_ref)


def _gmm_call(lhs, rhs, tile_group, *, transpose_rhs: bool, row_tile: int,
              interpret: bool, name: str):
    rows, k = lhs.shape
    groups = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if rows % row_tile or tile_group.shape != (rows // row_tile,):
        raise ValueError(f"{rows} rows do not split into the "
                         f"{tile_group.shape} tiles of {row_tile}")
    tn = _tile(n, _COL_TILE)
    held = lambda g, t: jnp.minimum(g[t], groups - 1)
    rhs_spec = (pl.BlockSpec((None, tn, k), lambda j, t, g: (held(g, t), j, 0))
                if transpose_rhs else
                pl.BlockSpec((None, k, tn), lambda j, t, g: (held(g, t), 0, j)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, groups=groups,
                          transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, rows // row_tile),
            in_specs=[pl.BlockSpec((row_tile, k), lambda j, t, g: (t, 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((row_tile, tn), lambda j, t, g: (t, j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(tile_group, lhs, rhs)


def _dw_kernel(group_ref, dout_ref, lhs_ref, out_ref, acc_ref, *,
               groups: int, tiles: int):
    t = pl.program_id(2)
    here = jnp.minimum(group_ref[t], groups - 1)
    before = jnp.minimum(group_ref[jnp.maximum(t - 1, 0)], groups - 1)
    after = jnp.minimum(group_ref[jnp.minimum(t + 1, tiles - 1)], groups - 1)

    @pl.when((t == 0) | (here != before))
    def _first_of_group():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(group_ref[t] < groups)
    def _accumulate():
        acc_ref[...] += _dot(dout_ref[...], lhs_ref[...], (0, 0))

    @pl.when((t == tiles - 1) | (here != after))
    def _last_of_group():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _dw_call(dout, lhs, tile_group, *, groups: int, row_tile: int,
             interpret: bool):
    rows, n = dout.shape
    k = lhs.shape[1]
    tiles = rows // row_tile
    tn, tk = _tile(n, _COL_TILE), _tile(k, _DW_IN_TILE)
    held = lambda g, t: jnp.minimum(g[t], groups - 1)
    out = pl.pallas_call(
        functools.partial(_dw_kernel, groups=groups, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, k // tk, tiles),
            in_specs=[pl.BlockSpec((row_tile, tn), lambda i, j, t, g: (t, i)),
                      pl.BlockSpec((row_tile, tk), lambda i, j, t, g: (t, j))],
            out_specs=pl.BlockSpec((None, tn, tk),
                                   lambda i, j, t, g: (held(g, t), i, j)),
            scratch_shapes=[pltpu.VMEM((tn, tk), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, n, k), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="penroz_moe_gmm_bwd_dw",
    )(tile_group, dout, lhs)
    # an expert no tile names was never written
    visited = jnp.zeros((groups,), jnp.bool_).at[tile_group].set(
        True, mode="drop")
    return jnp.where(visited[:, None, None], out, jnp.zeros_like(out))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, tile_group, row_tile, interpret):
    return _gmm_call(lhs, rhs, tile_group, transpose_rhs=True,
                     row_tile=row_tile, interpret=interpret,
                     name="penroz_moe_gmm_fwd")


def _gmm_fwd_rule(lhs, rhs, tile_group, row_tile, interpret):
    return (_gmm(lhs, rhs, tile_group, row_tile, interpret),
            (lhs, rhs, tile_group))


def _gmm_bwd_rule(row_tile, interpret, kept, dout):
    lhs, rhs, tile_group = kept
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm_call(dout, rhs, tile_group, transpose_rhs=False,
                     row_tile=row_tile, interpret=interpret,
                     name="penroz_moe_gmm_bwd_dx")
    drhs = _dw_call(dout, lhs, tile_group, groups=rhs.shape[0],
                    row_tile=row_tile, interpret=interpret)
    return dlhs, drhs.astype(rhs.dtype), None


_gmm.defvjp(_gmm_fwd_rule, _gmm_bwd_rule)


def grouped_matmul_kernel(lhs, rhs, tile_group, *,
                          row_tile: int = DEFAULT_ROW_TILE,
                          interpret: bool = False):
    """``out[t] = lhs[t] · rhs[tile_group[t]]ᵀ`` a tile of ``row_tile`` rows
    at a time, through the Pallas calls (differentiable in ``lhs`` and
    ``rhs``).  ``lhs`` ``(rows, in)``, ``rhs`` ``(groups, out, in)``,
    ``tile_group`` ``(rows // row_tile,)`` int32 ascending, ``groups`` for
    an empty tile (its rows come out zero)."""
    return _gmm(lhs, rhs, tile_group.astype(jnp.int32), int(row_tile),
                bool(interpret))


def grouped_matmul_ragged(lhs, rhs, tile_group, *,
                          row_tile: int = DEFAULT_ROW_TILE):
    """The same product by ``jax.lax.ragged_dot``: the groups' padded sizes
    are counted from ``tile_group``; the rows past them come out zero."""
    groups = rhs.shape[0]
    sizes = row_tile * jnp.zeros((groups,), jnp.int32).at[tile_group].add(
        1, mode="drop")
    out = jax.lax.ragged_dot(
        lhs, jnp.swapaxes(rhs, 1, 2), sizes,
        precision=_dot_precision(lhs.dtype),
        preferred_element_type=jnp.float32).astype(lhs.dtype)
    live = jnp.repeat(tile_group < groups, row_tile)
    return jnp.where(live[:, None], out, jnp.zeros_like(out))


def grouped_matmul(lhs, rhs, tile_group, *, row_tile: int = DEFAULT_ROW_TILE,
                   on_tpu: bool):
    """The dropless layer's product: the kernels on the TPU, ``ragged_dot``
    elsewhere."""
    if on_tpu:
        return grouped_matmul_kernel(lhs, rhs, tile_group, row_tile=row_tile)
    return grouped_matmul_ragged(lhs, rhs, tile_group, row_tile=row_tile)
