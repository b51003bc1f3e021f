"""Pallas TPU kernel for rotary position embeddings on q and k where they
lie, ``(B, T, heads · D)``: one pass, its own backward.

``ops/attention.py::apply_rope`` is the rotation written out: ``x · cos +
rotate_half(x) · sin`` on head-split ``(B, T, H, D)`` views.  On a TPU the
view is a relayout (``(…, H·D)`` tiles to ``(…, 16, 128)`` tiles and back),
the halves are slices, ``rotate_half`` a concatenate, the tables broadcast
over the heads and materialised: some twenty instructions and eight to nine
times the bytes of reading q and k once and writing them once, a rotation,
forward and backward alike (PERF.md §6, PR 49).

:func:`rotate` does it in that one pass.  A grid step owns a tile of whole
tokens by every lane of its arrays; a head is ``D`` lanes of the tile (one
register width at ``D`` = 128), and inside it::

    out = x · cos + roll(x, D/2 lanes) · sin±

where ``sin±`` is the sine table with the sign of ``rotate_half``'s first
half folded in (``roll`` brings ``x2`` under the first half and ``x1`` under
the second; the first wants ``-x2``).  ``x`` is widened in registers, the
tables are float32 ``(T, D)`` as ``rope_cos_sin`` makes them (so θ, the
offset, YaRN's or llama3's frequencies and the amplitude are that
function's; ``(B, T, D)`` where every row has its own offset), and the
result is rounded once to the input's type: nowhere a lower precision than
``apply_rope``'s, which multiplies in the input's type by tables of that
type.  q and k go through **one** call (a Pallas call is compiled and stored
an instance, and a looped program has 72).

Where no norm stands between the projection and the rotation the call takes
the fused ``(B, T, (heads + 2 · kv_heads) · D)`` projection as it is and
writes the three arrays the ``btd`` flash entry reads: q and k turned, v's
lanes carried across (the slice XLA would make of them).  After a qk-norm it
takes q and k as the two arrays the norm made.

The backward (``jax.custom_vjp``) is the same pass over the cotangents with
the sine's term subtracted: ``rotate_half`` is antisymmetric (the roll by
half a head is its own inverse, and ``sin±`` rolled by half a head is
``-sin±`` because ``rope_cos_sin``'s table repeats itself in its second
half).  No residual but the tables.  The fused form's backward reads the
three cotangents and writes the projection's in the same pass, so nothing
concatenates them: as two XLA instructions beside a kernel of q and k alone
(a slice of v, a concatenate of dq, dk, dv) the looped cell's program moved
22 GB more an optimizer step by the offline count (PERF.md §6, PR 49).

:func:`fits` says from the shapes who may come: whole heads of a multiple of
128 lanes, rotated over their whole width, whole 128-token tiles.  Everything
else (partial rotary, ``D`` = 64, the ``(B, H, T, D)`` path, a mesh, the CPU)
keeps ``apply_rope``; ``ops/modules.py::CausalSelfAttention.rope_plan``
decides and says which (``rope plan: path=kernel|xla …``).  ``path='jnp'``
runs the kernel's formula in ``jax.numpy`` and ``'interpret'`` the kernel
interpreted: the tests' (tests/test_rope.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Tokens a grid step owns: what every sequence the flash kernels take
# divides by.  The pass runs at the chip's bandwidth at 128, 256, 512 and
# 1024 alike (0.228 ms a call of the looped cell's 0.2 GB: PERF.md §6, PR 49).
TOKEN_TILE = 128
_VMEM_SLACK = 16 * 1024 * 1024      # a head's float32 temporaries


def fits(tokens: int, head_dim: int, rotary_dim: int | None = None) -> bool:
    """Whether the kernel takes sequences of ``tokens`` tokens with heads
    ``head_dim`` wide of which ``rotary_dim`` dims rotate (None: all)."""
    return (tokens % TOKEN_TILE == 0 and head_dim % LANES == 0
            and (rotary_dim is None or rotary_dim >= head_dim))


def moved_bytes(batch: int, tokens: int, heads: int, kv_heads: int,
                head_dim: int, itemsize: int) -> int:
    """What one rotation has to move: q and k read once, written once."""
    return 2 * batch * tokens * (heads + kv_heads) * head_dim * itemsize


def signed(sin):
    """``sin±``: the sine table with ``rotate_half``'s sign, ``-`` on the
    first half of a head's dims (a multiply by a constant: XLA makes it in
    the fusion that makes the table)."""
    D = sin.shape[-1]
    return sin * np.where(np.arange(D) < D // 2, -1.0, 1.0).astype(np.float32)


def _turned(x, cos, sin_signed, roll, back: bool = False):
    """``x`` ``(rows, D)`` rotated, float32; ``back``: by the opposite
    angle, the rotation's transpose."""
    x = x.astype(jnp.float32)
    other = roll(x, x.shape[-1] // 2, x.ndim - 1) * sin_signed
    return x * cos - other if back else x * cos + other


def _places(refs, pieces):
    """Where each piece lies in ``refs``: ``(ref, first lane)``; one array
    holds them side by side, several hold one each."""
    if len(refs) > 1:
        return [(ref, 0) for ref in refs]
    firsts = [sum(lanes for lanes, _ in pieces[:i])
              for i in range(len(pieces))]
    return [(refs[0], first) for first in firsts]


def _kernel(*refs, pieces: tuple, arrays_in: int, head_dim: int, back: bool):
    D = head_dim
    cos_ref, sin_ref = refs[arrays_in:arrays_in + 2]
    per_row = len(cos_ref.shape) == 3       # (1, tile, D): a row's own
    cos = cos_ref[0] if per_row else cos_ref[...]
    sin = sin_ref[0] if per_row else sin_ref[...]
    for (lanes, turns), (src, src0), (dst, dst0) in zip(
            pieces, _places(refs[:arrays_in], pieces),
            _places(refs[arrays_in + 2:], pieces)):
        if not turns:           # v's lanes, carried across
            dst[0, :, dst0:dst0 + lanes] = src[0, :, src0:src0 + lanes]
            continue

        def head(h, carry, src=src, src0=src0, dst=dst, dst0=dst0):
            at = lambda first: pl.ds(pl.multiple_of(first + h * D, LANES), D)
            dst[0, :, at(dst0)] = _turned(src[0, :, at(src0)], cos, sin,
                                          pltpu.roll, back).astype(dst.dtype)
            return carry
        jax.lax.fori_loop(0, lanes // D, head, 0)


def _pass(arrays, cos, sin_signed, *, heads: int, kv_heads: int, path: str,
          back: bool = False):
    """One pass: a tuple of arrays in, a tuple out.  One array in: the fused
    projection, split into ``(q_rot, k_rot, v)``; three in (the cotangents of
    those, ``back``): joined into the projection's; two in: ``(q, k)`` to
    ``(q_rot, k_rot)``."""
    B, T, _ = arrays[0].shape
    dtype = arrays[0].dtype
    D = (arrays[0].shape[-1] // (heads + 2 * kv_heads) if len(arrays) == 1
         else arrays[0].shape[-1] // heads)
    q_dim, kv_dim = heads * D, kv_heads * D
    pieces = ((q_dim, True), (kv_dim, True))        # (lanes, turned)
    if len(arrays) != 2:
        pieces += ((kv_dim, False),)                # v rides along
    widths = [lanes for lanes, _ in pieces]
    out_widths = [sum(widths)] if len(arrays) == 3 else widths
    if path == "jnp":
        if len(arrays) == 1:
            arrays = jnp.split(arrays[0], np.cumsum(widths)[:-1], axis=-1)
        tables = [t[..., None, :] for t in (cos, sin_signed)]
        out = [_turned(x.reshape(B, T, -1, D), *tables, jnp.roll, back)
               .astype(dtype).reshape(x.shape) if turns else x
               for x, (_, turns) in zip(arrays, pieces)]
        return (jnp.concatenate(out, axis=-1),) if len(out_widths) == 1 \
            else tuple(out)
    assert fits(T, D) and heads % kv_heads == 0, (T, D, heads, kv_heads)
    tile = TOKEN_TILE
    # the sequence's tiles outermost: a (T, D) table's block then stays
    # where it is while the batch's rows go by, and is fetched once a tile
    whole = lambda width: pl.BlockSpec((1, tile, width),
                                       lambda t, b: (b, t, 0))
    table = whole(D) if cos.ndim == 3 else pl.BlockSpec(
        (tile, D), lambda t, b: (t, 0))
    blocks = 4 * tile * sum(widths) * dtype.itemsize + 4 * tile * D * 4
    return tuple(pl.pallas_call(
        functools.partial(_kernel, pieces=pieces, arrays_in=len(arrays),
                          head_dim=D, back=back),
        grid=(T // tile, B),
        in_specs=[whole(a.shape[-1]) for a in arrays] + [table, table],
        out_specs=[whole(width) for width in out_widths],
        out_shape=[jax.ShapeDtypeStruct((B, T, width), dtype)
                   for width in out_widths],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=blocks + _VMEM_SLACK),
        cost_estimate=pl.CostEstimate(
            flops=3 * B * T * (q_dim + kv_dim), transcendentals=0,
            bytes_accessed=2 * B * T * sum(widths) * dtype.itemsize
            + 2 * cos.size * cos.dtype.itemsize),
        interpret=path == "interpret", name="penroz_rope",
    )(*arrays, cos, sin_signed))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rotate(arrays, cos, sin_signed, heads: int, kv_heads: int, path: str):
    return _pass(arrays, cos, sin_signed, heads=heads, kv_heads=kv_heads,
                 path=path)


def _rotate_fwd(arrays, cos, sin_signed, heads, kv_heads, path):
    return (_pass(arrays, cos, sin_signed, heads=heads, kv_heads=kv_heads,
                  path=path), (cos, sin_signed))


def _rotate_bwd(heads, kv_heads, path, tables, cotangents):
    return (_pass(cotangents, *tables, heads=heads, kv_heads=kv_heads,
                  path=path, back=True), None, None)


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def rotate(q, k, cos, sin, *, heads: int, kv_heads: int,
           path: str = "kernel"):
    """q and k rotated by the float32 tables ``cos``, ``sin`` ``(T, D)`` or
    ``(B, T, D)`` of ``ops/attention.py::rope_cos_sin``, in the input's type.
    ``q`` ``(B, T, heads·D)`` and ``k`` ``(B, T, kv_heads·D)`` give ``(q_rot,
    k_rot)``; ``k`` None and ``q`` the fused ``(B, T, (heads + 2·kv_heads)·
    D)`` projection give ``(q_rot, k_rot, v)``, three arrays for the ``btd``
    flash entry.  ``path``: ``kernel`` (shapes :func:`fits` admits, a TPU),
    ``interpret``, or ``jnp`` (the same formula, any shape)."""
    return _rotate((q,) if k is None else (q, k), cos.astype(jnp.float32),
                   signed(sin.astype(jnp.float32)), heads, kv_heads, path)
