"""Pallas TPU kernel for the passes of a multi-stream residual's mixing
(``ops/modules.py::HyperConnected``) over its state.

The state ``X`` of ``n`` streams is held stream-major, ``(B, n, T, d)``: a
stream of a sequence is one ``(T, d)`` slab, tokens on the sublanes and
features on the lanes, as every ``(B, T, d)`` activation of the model lies.
Everything the mixing does with it is one of four things a token at a
time, and :func:`token_mix` does any mix of them in **one pass** over a tile
of whole tokens (all ``n · d`` values of a token are in the tile, so every
reduction is local to it):

- *slabs out*: ``out = Σ_k coef[c_k] · slab_k`` — a per-token scalar (a row
  of ``coef`` ``(B, C, T)``, float32) against a slab's ``d`` lanes, summed
  in float32 and written in the slabs' type: ``x_in``, ``X'``, ``dy``, every
  ``dX``; optionally ``+ rows^T · W_j`` (``rows`` ``(B, M, T)`` against
  stream ``j``'s ``d`` columns of ``W`` ``(M, n·d)``, on the MXU: the
  ``Phi`` product's cotangent);
- *dots*: ``<slab_a, slab_b>`` a token, float32 — the sum of squares and the
  backward's inner products;
- *projection*: ``Σ_j W_j · slab_j^T`` ``(B, M, T)``, float32 accumulation on
  the MXU — the ``Phi`` product itself;
- *weight gradient*: ``rows · slab_j`` summed over all tokens, ``(M, n·d)``
  float32, accumulated in VMEM from the first tile to the last —
  ``dPhi``.

What is a few numbers a token (``coef``, ``rows``, the dots, the projection)
has **the tokens on the minor axis**, as the module's maps have them (an
``(n, n)`` matrix a token would pad 64-fold in the TPU's tiles): the kernel
turns a tile's ``(·, tile)`` block once, in registers, so no relayout of
them is left to XLA either.  Slabs are read as bfloat16 (or whatever they
are), widened in registers, and nothing of the state's size is written but
the slabs asked for: no float32 copy, no relayout.  The wrapper's callers
pass the state through ``jnp.swapaxes(X, 1, 2)``, which XLA folds into the
layout it holds ``X`` in.

A step walks its tile's lanes in a loop (``_CHUNK`` lanes a turn), so a
call's code is one turn's: a model makes eight calls a sub-block and every
one is compiled on its own (0.3–0.6 s each; unrolled over the lanes the
largest took 2.6 s and the cell's set-up 140 s more, PERF.md §6, PR 48).

Off the TPU, under a mesh and where :func:`fits` refuses the shapes the
module computes the same passes in ``jnp`` (``ops/modules.py::_token_mix``), which is what this
kernel is checked against in interpret mode (tests/test_xing.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TOKEN_TILE = 128        # tokens a grid step owns: one lane block of them
LANES = 128             # coefficient rows / dots / map rows a call may have
_CHUNK = 512            # lanes a turn of a step's loop covers
_VMEM_SLACK = 24 * 1024 * 1024
_VMEM_MOST = 96 * 1024 * 1024      # of a v5e core's 128 MiB


class Pass(NamedTuple):
    """What one call computes, in slab numbers (the input groups' slabs
    counted through in order).  ``outs``: a tuple of output groups, each a
    tuple of slabs, each a tuple of ``(coefficient row or None for 1,
    slab)`` terms; ``back``: for each output slab of every group, in order,
    the stream whose columns of ``W`` the ``rows`` product adds, or None;
    ``dots``: ``(a, b)`` pairs; ``proj``: the slabs of the projection, one a
    stream, or (); ``wgrad``: the slabs of the weight gradient, one a
    stream, or () (it takes ``rows`` as ``back`` does)."""
    outs: tuple = ()
    back: tuple = ()
    dots: tuple = ()
    proj: tuple = ()
    wgrad: tuple = ()


def _vmem(slabs: int, features: int, itemsize: int, resident: int = 0) -> int:
    """What a call asks the compiler for: its slabs double-buffered, what
    stays resident, and room for the turn's float32 temporaries."""
    return (2 * slabs * TOKEN_TILE * features * itemsize + resident
            + _VMEM_SLACK)


def fits(tokens: int, features: int, streams: int, itemsize: int) -> bool:
    """Whether the kernel's tiles admit sequences of ``tokens`` tokens of
    ``features`` features a stream: whole lane blocks of both, and the
    largest pass of ``streams`` streams (the writing half's backward: 3 ·
    streams + 2 slabs) within what a call may ask of the core's VMEM."""
    return (tokens % TOKEN_TILE == 0 and features % LANES == 0
            and _vmem(3 * streams + 2, features, itemsize,
                      3 * 4 * LANES * streams * features) <= _VMEM_MOST)


def _chunk(d: int) -> int:
    return _CHUNK if d % _CHUNK == 0 else LANES


def _dot(a, b, contract=(1, 0)):
    """float32-accumulated ``a · b`` on the MXU contracting ``contract =
    (dim of a, dim of b)``, as the flash kernels take theirs: float32
    operands at full precision, bfloat16 as they are."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                   else jax.lax.Precision.DEFAULT))


def _kernel(*refs, plan: Pass, sizes: tuple, has_coef: bool, d: int):
    groups = refs[:len(sizes)]
    rest = list(refs[len(sizes):])
    coef = rest.pop(0)[0].T if has_coef else None           # (tile, LANES)
    rows = rest.pop(0)[0] if (plan.back or plan.wgrad) else None
    w = rest.pop(0) if (plan.back or plan.proj) else None
    out_refs = [rest.pop(0) for _ in plan.outs]
    dots_ref = rest.pop(0) if plan.dots else None
    proj_ref = rest.pop(0) if plan.proj else None
    wgrad_ref = rest.pop(0) if plan.wgrad else None
    if rows is not None:    # (LANES, tile), the MXU's operand type as XLA's
        rows = rows.astype(groups[0].dtype)
    if plan.wgrad:
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _():
            wgrad_ref[...] = jnp.zeros_like(wgrad_ref)

    where = [(g, j) for g, size in enumerate(sizes) for j in range(size)]
    # a token's scalars (tile, 1), made before the loop: its constants
    column = {c: coef[:, c:c + 1] for c in sorted({
        c for group in plan.outs for terms in group for c, _ in terms
        if c is not None})}
    tile = groups[0].shape[2]
    back = iter(plan.back)
    stream_of = [[next(back) for _ in group] for group in plan.outs] \
        if plan.back else [[None] * len(group) for group in plan.outs]
    width = _chunk(d)

    def turn(c, state):
        partial, proj = state
        lo = pl.multiple_of(c * width, width)
        lanes = pl.ds(lo, width)
        loaded = {}

        def slab(s, wide=True):
            if (s, wide) not in loaded:
                g, j = where[s]
                value = groups[g][0, j, :, lanes]
                loaded[s, wide] = value.astype(jnp.float32) if wide else value
            return loaded[s, wide]

        of = lambda stream: pl.ds(pl.multiple_of(stream * d + lo, LANES),
                                  width)
        for ref, group, streams in zip(out_refs, plan.outs, stream_of):
            for i, (terms, stream) in enumerate(zip(group, streams)):
                acc = None
                for k, s in terms:
                    term = slab(s) if k is None else column[k] * slab(s)
                    acc = term if acc is None else acc + term
                if stream is not None:
                    acc = acc + _dot(rows, w[:, of(stream)], (0, 0))
                ref[0, i, :, lanes] = acc.astype(ref.dtype)
        for j, s in enumerate(plan.wgrad):
            wgrad_ref[:, of(j)] += _dot(rows, slab(s, wide=False))
        partial = list(partial)
        for k, (a, b) in enumerate(plan.dots):
            prod = slab(a) * slab(b)
            for at in range(0, width, LANES):
                partial[k] = partial[k] + prod[:, at:at + LANES]
        for j, s in enumerate(plan.proj):
            proj = proj + _dot(w[:, of(j)], slab(s, wide=False), (1, 1))
        return tuple(partial), proj

    partial, proj = jax.lax.fori_loop(0, d // width, turn, (
        tuple(jnp.zeros((tile, LANES), jnp.float32) for _ in plan.dots),
        jnp.zeros((LANES, tile) if plan.proj else (), jnp.float32)))
    if plan.dots:
        lane = jax.lax.broadcasted_iota(jnp.int32, (tile, LANES), 1)
        dots = jnp.zeros((tile, LANES), jnp.float32)
        for k, part in enumerate(partial):
            dots = jnp.where(lane == k,
                             jnp.sum(part, axis=1, keepdims=True), dots)
        dots_ref[0] = dots.T
    if plan.proj:
        proj_ref[0] = proj


def token_mix(groups: Sequence[jax.Array], plan: Pass, *, coef=None,
              rows=None, w=None, interpret: bool = False):
    """One pass over ``groups`` (arrays ``(B, g, T, d)``) as ``plan`` says:
    ``[output groups (B, len(group), T, d) in the groups' type…, dots (B,
    len(dots), T) float32 if any, projection (B, M, T) float32 if any, weight
    gradient (M, len(wgrad)·d) float32 if any]``.  ``coef`` ``(B, C, T)``
    float32; ``rows`` ``(B, M, T)`` float32 for ``plan.back`` and
    ``plan.wgrad``; ``w`` ``(M, n·d)`` for ``plan.back`` or ``plan.proj``."""
    B, _, T, d = groups[0].shape
    tile = TOKEN_TILE
    assert T % tile == 0 and d % LANES == 0, (T, d)
    sizes = tuple(g.shape[1] for g in groups)
    out_dtype = groups[0].dtype
    pad = lambda t: jnp.pad(t.astype(jnp.float32),
                            ((0, 0), (0, LANES - t.shape[1]), (0, 0)))
    slabs_of = lambda size: pl.BlockSpec((1, size, tile, d),
                                         lambda b, t: (b, 0, t, 0))
    per_token = pl.BlockSpec((1, LANES, tile), lambda b, t: (b, 0, t))
    whole = lambda shape: pl.BlockSpec(shape, lambda b, t: (0, 0))
    operands, in_specs = list(groups), [slabs_of(size) for size in sizes]
    resident = 0
    if coef is not None:
        operands.append(pad(coef))
        in_specs.append(per_token)
    if plan.back or plan.wgrad:
        M = rows.shape[1]
        operands.append(pad(rows))
        in_specs.append(per_token)
    if plan.back or plan.proj:
        M = w.shape[0]
        w = jnp.pad(w, ((0, LANES - M), (0, 0)))
        operands.append(w)
        in_specs.append(whole(w.shape))
        resident += 2 * w.size * w.dtype.itemsize
    out_shape = [jax.ShapeDtypeStruct((B, len(group), T, d), out_dtype)
                 for group in plan.outs]
    out_specs = [slabs_of(len(group)) for group in plan.outs]
    for wanted in (plan.dots, plan.proj):
        if wanted:
            out_shape.append(jax.ShapeDtypeStruct((B, LANES, T),
                                                  jnp.float32))
            out_specs.append(per_token)
    if plan.wgrad:
        shape = (LANES, len(plan.wgrad) * d)
        out_shape.append(jax.ShapeDtypeStruct(shape, jnp.float32))
        out_specs.append(whole(shape))
        resident += 2 * 4 * shape[0] * shape[1]
    slabs = sum(sizes) + sum(len(group) for group in plan.outs)
    results = pl.pallas_call(
        functools.partial(_kernel, plan=plan, sizes=sizes,
                          has_coef=coef is not None, d=d),
        grid=(B, T // tile), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            # the weight gradient is one block for every step: in order
            dimension_semantics=(("arbitrary",) if plan.wgrad
                                 else ("parallel",)) * 2,
            vmem_limit_bytes=_vmem(slabs, d, out_dtype.itemsize, resident)),
        interpret=interpret, name="penroz_hc_mix")(*operands)
    results = list(results)
    at = len(plan.outs)
    if plan.dots:
        results[at] = results[at][:, :len(plan.dots)]
        at += 1
    if plan.proj:
        results[at] = results[at][:, :M]
        at += 1
    if plan.wgrad:
        results[at] = results[at][:M]
    return results
