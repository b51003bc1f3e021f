"""Every main-path Pallas kernel compiles for a TPU v5e at GPT-2 124M widths.

Interpret mode (the other kernel tests) runs the kernel body as jnp and
accepts layouts Mosaic refuses: a block whose last two dims are neither
(8, 128)-divisible nor the array's own, more VMEM than a kernel may hold.
The TPU compiler is installed here and compiles for a chip that is
*described*, not attached (``jax.experimental.topologies``), so these
cases ask it directly — no chip time, ~2 s each.  Nothing runs: a pass
says the chip's compiler accepts the program, never that its output is
right (``chip_smoke.py`` checks that on the chip against the jnp oracles).

Shapes: 12 heads, D = 64, T = 1024, vocab 50304, 8 decode rows, and the
page sizes the README launches use (8, 16) plus the 128-token default.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
# Only the compiler is used, no chip is opened: let every xdist worker load
# libtpu at once instead of failing on its one-process lockfile.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

HEADS, HEAD_DIM, BLOCK, VOCAB, ROWS = 12, 64, 1024, 50304, 8


@pytest.fixture(scope="module")
def chips():
    """The four described chips of a v5e 2x2 host; compile cache off around
    the module (a described-topology entry can be written but not read back
    without a chip, so a warm cache only adds a warning per compile)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"TPU topology cannot be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(chips):
    return SingleDeviceSharding(chips[0])


def _pool(page, dtype):
    """(flat pool, per-token scale plane, block table) shapes of an
    8-row × 1024-token paged cache."""
    rows = ROWS * BLOCK
    return ((HEADS, rows, HEAD_DIM), dtype), ((HEADS, rows, 1), jnp.float32), \
        ((ROWS, BLOCK // page), jnp.int32)


CELL_ROWS = 12      # gpt2s-train-1chip's micro-batch: (12, 12, 1024, 64)


def _flash_fwd(q=(ROWS, HEADS, BLOCK, HEAD_DIM), kv=None, **kwargs):
    from penroz_tpu.ops.pallas import flash_attention as fa
    shapes = [(q, jnp.bfloat16)] + [(kv or q, jnp.bfloat16)] * 2
    return (lambda q, k, v: fa.flash_attention(q, k, v, **kwargs)), shapes


def _flash_bwd(q=(ROWS, HEADS, BLOCK, HEAD_DIM), kv=None, **kwargs):
    from penroz_tpu.ops.pallas import flash_attention as fa
    shapes = [(q, jnp.bfloat16)] + [(kv or q, jnp.bfloat16)] * 2

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, **kwargs).astype(
            jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), shapes


def _flash_btd(backward, shape, dtype=jnp.bfloat16, **kwargs):
    """``flash_attention_btd`` on the fused ``(B, T, (Hq + 2·Hkv)·D)``
    projection, or its gradient."""
    from penroz_tpu.ops.pallas import flash_attention as fa

    def attend(qkv):
        return fa.flash_attention_btd(qkv, **kwargs)

    def loss(qkv):
        return attend(qkv).astype(jnp.float32).sum()

    return (jax.grad(loss) if backward else attend), [(shape, dtype)]


def _decode(quantized, dtype=jnp.bfloat16):
    from penroz_tpu.ops.pallas import decode_attention as da
    kv_dtype = jnp.int8 if quantized else dtype
    q = ((ROWS, HEADS, 1, HEAD_DIM), dtype)
    kv = ((ROWS, HEADS, BLOCK, HEAD_DIM), kv_dtype)
    lengths = ((ROWS,), jnp.int32)
    if not quantized:
        return (lambda q, k, v, n: da.decode_attention(q, k, v, 0, n)), \
            [q, kv, kv, lengths]
    scale = ((ROWS, HEADS, BLOCK, 1), jnp.float32)
    return (lambda q, k, v, n, ks, vs: da.decode_attention(
        q, k, v, 0, n, k_scale=ks, v_scale=vs)), \
        [q, kv, kv, lengths, scale, scale]


def _paged(page, quantized):
    from penroz_tpu.ops.pallas import paged_attention as pa
    pool, scale, table = _pool(page, jnp.int8 if quantized else jnp.bfloat16)
    q = ((ROWS, HEADS, 1, HEAD_DIM), jnp.bfloat16)
    lengths = ((ROWS,), jnp.int32)
    if not quantized:
        return (lambda q, k, v, t, n: pa.paged_decode_attention(
            q, k, v, t, page, 0, n)), [q, pool, pool, table, lengths]
    return (lambda q, k, v, t, n, ks, vs: pa.paged_decode_attention(
        q, k, v, t, page, 0, n, k_scale=ks, v_scale=vs)), \
        [q, pool, pool, table, lengths, scale, scale]


def _ragged(page, quantized, descs=40, block_q=8, dtype=jnp.bfloat16):
    """A unified tick: 8 decode rows + a 256-token prefill chunk = 40
    descriptors of block_q = 8 packed query slots."""
    from penroz_tpu.ops.pallas import ragged_paged_attention as rpa
    pool, scale, table = _pool(page, jnp.int8 if quantized else dtype)
    q = ((1, HEADS, descs * block_q, HEAD_DIM), dtype)
    d = ((descs, rpa.DESC_COLS), jnp.int32)
    if not quantized:
        return (lambda q, k, v, t, d: rpa.ragged_paged_attention(
            q, k, v, t, page, d)), [q, pool, pool, table, d]
    return (lambda q, k, v, t, d, ks, vs: rpa.ragged_paged_attention(
        q, k, v, t, page, d, k_scale=ks, v_scale=vs)), \
        [q, pool, pool, table, d, scale, scale]


def _ce_fwd():
    from penroz_tpu.ops.pallas import cross_entropy as ce
    return ce.ce_forward, [((ROWS * BLOCK, VOCAB), jnp.bfloat16),
                           ((ROWS * BLOCK,), jnp.int32)]


def _ce_bwd():
    from penroz_tpu.ops.pallas import cross_entropy as ce
    return ce.ce_backward, [((ROWS * BLOCK, VOCAB), jnp.bfloat16),
                            ((ROWS * BLOCK,), jnp.int32),
                            ((ROWS * BLOCK, 1), jnp.float32),
                            ((ROWS * BLOCK, 1), jnp.float32)]   # a row each


def _ssm_scan():
    """presets.hybrid_custom at GPT-2 124M widths: dk = dv = d / heads."""
    from penroz_tpu.ops.pallas import ssm_scan
    qkv = ((ROWS, BLOCK, HEADS, HEAD_DIM), jnp.bfloat16)
    return ssm_scan.gla_chunked, [qkv] * 3 + [((ROWS, BLOCK, HEADS),
                                               jnp.float32)]


def _rope(backward, shapes, heads, kv_heads, dtype=jnp.bfloat16,
          per_row=False):
    """``ops/pallas/rope.py::rotate`` on a fused projection (one shape) or
    on q and k apart (two), forward or value and gradient; ``per_row``: a
    table a row, ``(B, T, D)``."""
    from penroz_tpu.ops import attention as attn_ops
    from penroz_tpu.ops.pallas import rope
    B, T, width = shapes[0]
    D = width // (heads + 2 * kv_heads if len(shapes) == 1 else heads)

    def turn(*arrays):
        offset = jnp.arange(B) * 3 if per_row else 0
        cos, sin = attn_ops.rope_cos_sin(D, 1e4, offset, T, jnp.float32)
        return rope.rotate(*arrays, *[None] * (2 - len(arrays)), cos, sin,
                           heads=heads, kv_heads=kv_heads)

    def loss(*arrays):
        return sum((o.astype(jnp.float32) ** 2).sum() for o in turn(*arrays))

    fn = jax.grad(loss, argnums=tuple(range(len(shapes)))) if backward \
        else turn
    return fn, [(shape, dtype) for shape in shapes]


CASES = {
    "flash_fwd": _flash_fwd,
    "flash_bwd": _flash_bwd,
    # the benchmark cell's own shape: K/V resident, one-pass backward
    "flash_fwd_cell": lambda: _flash_fwd((CELL_ROWS, HEADS, BLOCK, HEAD_DIM)),
    "flash_bwd_cell": lambda: _flash_bwd((CELL_ROWS, HEADS, BLOCK, HEAD_DIM)),
    # long context, D = 128, 4 query heads a K/V head: resident forward on
    # part of the queries a step, one-pass backward under the limit its plan
    # asks for (24.1 MiB: the compiler's default refuses it)
    "flash_fwd_t4096_gqa": lambda: _flash_fwd((2, 8, 4096, 128),
                                              (2, 2, 4096, 128)),
    "flash_bwd_t4096_gqa": lambda: _flash_bwd((2, 8, 4096, 128),
                                              (2, 2, 4096, 128)),
    # … at the longest the limit takes (41.75 MiB) and past it, where the
    # two kernels still serve
    "flash_bwd_t8192": lambda: _flash_bwd((1, 4, 8192, 128)),
    "flash_bwd_t16384_split": lambda: _flash_bwd((1, 2, 16384, 128)),
    # the chunked kernels a long S falls to, with a window's clamped walks
    "flash_fwd_chunked": lambda: _flash_fwd(window=700, vmem_budget=2 ** 20),
    "flash_bwd_chunked": lambda: _flash_bwd(window=700, vmem_budget=2 ** 20),
    # the (B, T, lanes) layout.  The cell's own call: the fused projection,
    # two D = 64 heads a lane block
    "flash_btd_fwd_cell": lambda: _flash_btd(
        False, (CELL_ROWS, BLOCK, 3 * HEADS * HEAD_DIM), heads=HEADS),
    "flash_btd_bwd_cell": lambda: _flash_btd(
        True, (CELL_ROWS, BLOCK, 3 * HEADS * HEAD_DIM), heads=HEADS),
    # … under a window narrower than a tile: the tiles on the diagonal cut
    # into sub-blocks, those left of the window dropped, the rest masked
    "flash_btd_fwd_cell_window": lambda: _flash_btd(
        False, (CELL_ROWS, BLOCK, 3 * HEADS * HEAD_DIM), heads=HEADS,
        window=200),
    "flash_btd_bwd_cell_window": lambda: _flash_btd(
        True, (CELL_ROWS, BLOCK, 3 * HEADS * HEAD_DIM), heads=HEADS,
        window=200),
    # D = 128, 4 query heads a K/V head: one head a block, any group
    "flash_btd_fwd_d128_gqa": lambda: _flash_btd(
        False, (2, BLOCK, 12 * 128), heads=8, kv_heads=2),
    "flash_btd_bwd_d128_gqa": lambda: _flash_btd(
        True, (2, BLOCK, 12 * 128), heads=8, kv_heads=2),
    # … at T = 4096: resident forward on part of the queries, one-pass
    # backward under its limit; at T = 16384 the split
    "flash_btd_bwd_t4096_gqa": lambda: _flash_btd(
        True, (2, 4096, 12 * 128), heads=8, kv_heads=2),
    "flash_btd_bwd_t16384_split": lambda: _flash_btd(
        True, (1, 16384, 6 * 128), heads=4, kv_heads=1),
    # float32 (a model created through POST /model/ trains in it, and
    # ``chip_smoke.py`` does): the one pass's estimate is past the default
    # share from T = 1024, and Mosaic's own need is past the estimate (16.4
    # MiB of 14.7 with every feature on): the margin of what is asked for
    "flash_btd_bwd_f32": lambda: _flash_btd(
        True, (ROWS, BLOCK, 3 * HEADS * HEAD_DIM), jnp.float32, heads=HEADS),
    "flash_btd_bwd_f32_features": lambda: _flash_btd(
        True, (ROWS, BLOCK, 3 * HEADS * HEAD_DIM), jnp.float32, heads=HEADS,
        window=700, dropout_rate=0.1, seed=3,
        alibi=[2.0 ** -(i + 1) for i in range(HEADS)]),
    "flash_btd_bwd_f32_t4096": lambda: _flash_btd(
        True, (1, 4096, 6 * 128), jnp.float32, heads=4, kv_heads=1),
    # the chunked kernels with head pairs, a window's clamped walks
    "flash_btd_fwd_chunked": lambda: _flash_btd(
        False, (ROWS, BLOCK, 3 * HEADS * HEAD_DIM), heads=HEADS, window=700,
        vmem_budget=2 ** 20),
    "flash_btd_bwd_chunked": lambda: _flash_btd(
        True, (ROWS, BLOCK, 3 * HEADS * HEAD_DIM), heads=HEADS, window=700,
        vmem_budget=2 ** 20),
    # the rotation where q and k lie (PR 49).  The looped cell's fused
    # projection, 16 + 16 + 16 heads of 128
    "rope_fwd_loop4k": lambda: _rope(False, [(2, 4096, 6144)], 16, 16),
    "rope_bwd_loop4k": lambda: _rope(True, [(2, 4096, 6144)], 16, 16),
    # Laguna's sliding layers: 9 query heads on one K/V head, T = 8192
    "rope_bwd_9on1_t8192": lambda: _rope(True, [(1, 8192, 11 * 128)], 9, 1),
    # after a qk-norm: q and k apart; a head two registers wide; float32,
    # a table a row
    "rope_bwd_apart": lambda: _rope(
        True, [(2, 1024, 8 * 128), (2, 1024, 2 * 128)], 8, 2),
    "rope_bwd_d256": lambda: _rope(True, [(2, 1024, 8 * 256)], 4, 2),
    "rope_bwd_f32_per_row": lambda: _rope(
        True, [(2, 1024, 3 * HEADS * 128)], HEADS, HEADS, jnp.float32,
        per_row=True),
    "decode_bf16": lambda: _decode(False),
    "decode_int8": lambda: _decode(True),
    "paged_bf16_page128": lambda: _paged(128, False),
    "paged_int8_page128": lambda: _paged(128, True),
    "paged_bf16_page16": lambda: _paged(16, False),
    "paged_int8_page16": lambda: _paged(16, True),
    "ragged_bf16_page128": lambda: _ragged(128, False),
    "ragged_int8_page128": lambda: _ragged(128, True),
    "ragged_bf16_page16": lambda: _ragged(16, False),
    "ragged_int8_page16": lambda: _ragged(16, True),
    "ragged_bf16_page8": lambda: _ragged(8, False),
    "ragged_int8_page8": lambda: _ragged(8, True),
    "ce_fwd": _ce_fwd,
    "ce_bwd": _ce_bwd,
    "ssm_scan": _ssm_scan,
    # what a model created through POST /model/ serves in: fp32 params,
    # fp32 cache (HF imports are bf16)
    "decode_f32": lambda: _decode(False, jnp.float32),
    "ragged_f32_page16": lambda: _ragged(16, False, dtype=jnp.float32),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(chip, name):
    fn, shapes = CASES[name]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, \
        f"{name}: no Mosaic custom call in the compiled program"
    if "flash" in name and "bwd" in name:
        # which backward a shape gets: the two kernels only where the case
        # says so (a narrowed budget, a length past the limit)
        split = name.endswith(("_split", "_chunked"))
        assert ("penroz_flash_bwd_dq" in hlo) == split, name
        assert ("penroz_flash_bwd_dkv" in hlo) == split, name


@pytest.mark.parametrize("grain,want", [(512, 1.5), (256, 1.25),
                                        (128, 1.125)])
def test_cell_walk_computes_this_much_over_its_band(grain, want):
    """``computed_over_live`` of the cell's attention (T = 1024, 512-tiles,
    causal): three tiles for a band of two when the two on the diagonal are
    done whole, 2.5 and 2.25 when they are cut at 256 and 128 — and the
    plan the cell gets is one of the cut ones."""
    from penroz_tpu.ops.pallas import flash_attention as fa
    assert fa.computed_over_live(BLOCK, BLOCK, 512, 512, grain, True,
                                 None) == want
    plan = fa.plan_flash(BLOCK, BLOCK, HEAD_DIM, 2, heads=HEADS,
                         layout="btd", fused_qkv=True)
    assert (plan.block_q, plan.block_k) == (512, 512)
    for g, ratio in ((plan.diag_grain, plan.computed_over_live),
                     (plan.bwd_diag_grain, plan.bwd_computed_over_live)):
        assert g < 512 and ratio <= 1.25
        assert ratio == fa.computed_over_live(BLOCK, BLOCK, 512, 512, g, True,
                                              None)


def test_btd_group_sum_adds_lane_ranges(chip):
    """dK and dV of grouped heads in the ``(B, T, lanes)`` layout: the sum
    over each K/V head's group is adds of 128-lane ranges inside fusions.
    No array splits the lanes into ``(group, D)`` — that reshape relays the
    whole per-query-head dK and dV out (0.29 of a 3.8 ms layer on the chip,
    PERF.md §6, PR 32) — and nothing is reshaped, copied or transposed."""
    fn, shapes = CASES["flash_btd_bwd_d128_gqa"]()
    hlo = jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype in shapes)).compile().as_text()
    assert "[2,1024,2,4,128]" not in hlo
    for op in (" reshape(", " copy(", " transpose("):
        assert op not in hlo, op


# -- the same kernels inside a program GSPMD partitions over four chips -------
#
# Mosaic refuses a kernel call under jit over more than one device ("cannot
# be automatically partitioned"), so the dispatchers in ops/attention.py map
# the kernel over the mesh named by their ``platform`` hint.  These go
# through those dispatchers, as the model does.

def _mesh_train(hint, rows=ROWS):
    """Flash fwd+bwd and fused CE fwd+bwd, batch split over ``data``."""
    from penroz_tpu.ops import attention as A
    from penroz_tpu.ops import losses
    qkv = ((rows, HEADS, BLOCK, HEAD_DIM), jnp.bfloat16, P("data", "model"))
    w = ((HEADS * HEAD_DIM, 2048), jnp.bfloat16, P())
    y = ((rows, BLOCK), jnp.int32, P("data"))

    def loss(q, k, v, w, y):
        out = A.causal_attention(q, k, v, platform=hint)
        logits = out.transpose(0, 2, 1, 3).reshape(rows, BLOCK, -1) @ w
        return losses.fused_cross_entropy_mean(logits, y, 512, hint)

    return jax.grad(loss, argnums=(0, 1, 2, 3)), [qkv, qkv, qkv, w, y]


def _mesh_train_cell(hint):
    """The cell's micro-batch over ``data=4``: 3 rows a chip (the planned
    ``gpt2s-train-dp4``)."""
    return _mesh_train(hint, CELL_ROWS)


def _mesh_train_btd(hint):
    """The cell's attention in the model's own layout over ``data=4``: the
    fused projection's batch split, 3 rows a chip, forward and backward."""
    from penroz_tpu.ops import attention as A
    qkv = ((CELL_ROWS, BLOCK, 3 * HEADS * HEAD_DIM), jnp.bfloat16, P("data"))

    def loss(qkv):
        return A.causal_attention_btd(
            qkv, heads=HEADS, kv_heads=HEADS, platform=hint).astype(
                jnp.float32).sum()

    return jax.grad(loss), [qkv]


def _mesh_ragged(hint):
    """The unified serving tick, heads split over ``model``."""
    from penroz_tpu.ops import attention as A
    _, shapes = _ragged(16, False, dtype=jnp.float32)
    specs = [P(None, "model"), P("model"), P("model"), P(), P()]
    return (lambda q, k, v, t, d: A.ragged_paged_cached_attention(
        q, k, v, t, 16, d, platform=hint)), \
        [(*shape, spec) for shape, spec in zip(shapes, specs)]


@pytest.mark.parametrize("case,axes", [(_mesh_train, {"data": 4}),
                                       (_mesh_train_cell, {"data": 4}),
                                       (_mesh_train_btd, {"data": 4}),
                                       (_mesh_train, {"model": 2}),
                                       (_mesh_ragged, {"model": 4})])
def test_kernels_compile_partitioned_for_v5e(chips, case, axes):
    from penroz_tpu.ops.attention import Placement
    from penroz_tpu.parallel import mesh as mesh_lib
    mesh = mesh_lib.make_mesh(chips, model=axes.get("model", 1))
    fn, shapes = case(Placement("tpu", mesh))
    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(mesh, spec))
            for shape, dtype, spec in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _embedding_backward(hint, spec):
    """The embedding lookup's backward as the model takes it, at
    gpt2s-train-1chip's micro-batch: (V, N, d) = (50304, 12288, 768)."""
    from penroz_tpu.ops import modules as M
    width = HEADS * HEAD_DIM
    shapes = [((VOCAB, width), jnp.bfloat16, P()),
              ((CELL_ROWS, BLOCK), jnp.int32, spec),
              ((CELL_ROWS, BLOCK, width), jnp.bfloat16, spec)]

    def loss(t, ids, cot):
        return (M._gather_rows(t, ids, VOCAB, "bfloat16", hint)
                * cot).astype(jnp.float32).sum()

    return jax.grad(loss), shapes


def test_embedding_backward_is_a_native_scatter_on_one_chip(chip):
    """``modules._scatter_rows_grad`` rests on what the v5e compiler makes
    of a row scatter-add: one ``scatter`` inside a fusion, no ``while``
    walking the 12 288 update rows (it once was one, and the one-hot matmul
    was written to avoid it).  A compiler that goes back to the loop fails
    here, before a chip shows it as a slower step."""
    fn, shapes = _embedding_backward("tpu", P())
    hlo = jax.jit(fn).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype, _ in shapes)).compile().as_text()
    assert " scatter(" in hlo
    assert " while(" not in hlo and " convolution(" not in hlo


def test_embedding_backward_under_a_mesh_gathers_rows_not_tables(chips):
    """Under ``data=4`` the backward stays on the one-hot scan: GSPMD
    gathers the ids and the cotangent rows for it and moves no (V, d) table
    between chips (the scatter would get an all-reduce of bf16 partial
    tables)."""
    from penroz_tpu.ops.attention import Placement
    from penroz_tpu.parallel import mesh as mesh_lib
    mesh = mesh_lib.make_mesh(chips, model=1)
    fn, shapes = _embedding_backward(Placement("tpu", mesh), P("data"))
    hlo = jax.jit(fn, out_shardings=NamedSharding(mesh, P())).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))
        for shape, dtype, spec in shapes)).compile().as_text()
    assert " scatter(" not in hlo and " convolution(" in hlo
    assert "all-gather" in hlo
    assert "all-reduce" not in hlo and "reduce-scatter" not in hlo


def _epoch_hlo_groups():
    """``scripts/epoch_hlo_groups.py``, loaded from its file."""
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "epoch_hlo_groups", os.path.join(root, "scripts",
                                         "epoch_hlo_groups.py"))
    groups = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(groups)
    return groups


def _custom_calls(hlo: str) -> list:
    """(instruction name, result types) of every Mosaic custom call."""
    calls = []
    for line in hlo.splitlines():
        head, call, _ = line.strip().partition(" custom-call(")
        if call and "tpu_custom_call" in line:
            calls.append(head.removeprefix("ROOT ").partition(" = ")[::2])
    return calls


def _attention_block(chip):
    """(attend, qkv, w): the cell's ``CausalSelfAttention`` as a function of
    the fused ``(12, 1024, 2304)`` projection, and the shapes of that
    projection and of a projection matrix after it, on ``chip``."""
    from penroz_tpu.ops import modules as M
    width = HEADS * HEAD_DIM
    attn = M.CausalSelfAttention(num_heads=HEADS)
    attn.bind("attn")
    qkv = jax.ShapeDtypeStruct((CELL_ROWS, BLOCK, 3 * width), jnp.bfloat16,
                               sharding=chip)
    w = jax.ShapeDtypeStruct((width, width), jnp.bfloat16, sharding=chip)
    return (lambda qkv: attn.apply(qkv, M.Ctx({}, platform="tpu"))), qkv, w


@pytest.fixture(scope="module")
def attention_block_hlo(chip):
    """The gradient of one attention block of the cell —
    ``CausalSelfAttention`` on the fused ``(12, 1024, 2304)`` projection,
    then a projection matmul — compiled for a v5e."""
    attend, qkv, w = _attention_block(chip)

    def loss(qkv, w):
        return (attend(qkv) @ w).astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        qkv, w).compile().as_text()


def test_attention_block_gradient_stays_in_the_models_layout(
        attention_block_hlo):
    """The mechanism of PR 32, checked without a chip: the compiled
    gradient of the cell's attention block holds the flash kernels under
    their names (``penroz_flash_fwd``, ``penroz_flash_bwd_delta``,
    ``penroz_flash_bwd``) with ``(B, T, H·D)`` results, the fused
    projection's cotangent, and **no** head-major array: nothing
    ``bf16[12,12,1024,64]`` (a transposed q, k, v, o or their cotangents,
    stored padded to 128 lanes) and no ``f32[12,12,1024,1]`` logsumexp
    anywhere in the program."""
    import re
    hlo = attention_block_hlo
    calls = _custom_calls(hlo)
    out = r"bf16\[12,1024,768\]"
    fwd = [c for c in calls if "penroz_flash_fwd" in c[0]]
    bwd = [c for c in calls if "penroz_flash_bwd" in c[0]]
    assert len(fwd) == 1 and re.search(out + r".*f32\[12,6,2,1024\]",
                                       fwd[0][1]), calls
    # the backward: its δ rows, then one pass that returns dq, dk, dv
    assert [len(re.findall(out, c[1])) for c in bwd] == [0, 3], calls
    assert re.search(r"f32\[12,6,2,1024\]", bwd[0][1]), calls
    assert len(calls) == 3, calls
    assert re.search(r"bf16\[12,1024,2304\]", hlo)    # the fused cotangent
    assert not re.search(r"bf16\[12,12,1024,64\]", hlo)
    assert not re.search(r"f32\[12,12,1024,1\]", hlo)
    assert " transpose(" not in hlo


def test_cell_kernels_are_where_the_benchmark_looks_for_them(
        attention_block_hlo):
    """``benchmark/metrics/penroz_flash_roofline.py`` — the reader itself,
    loaded from its file — finds the cell's kernels in a device trace made
    of this program's instructions (a trace event carries its HLO
    instruction's text): the forward and both backward calls, by name.
    Renaming a call, or losing the name on the way to the HLO, blinds the
    benchmark and fails here, on the CPU.  The old reader,
    ``flash_roofline_pct``, matches head-major result shapes and finds
    nothing in this program (the test below pins what it does find)."""
    import importlib.util
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        readers = {}
        for name in ("penroz_flash_roofline", "flash_roofline_pct"):
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + name,
                os.path.join(root, "benchmark", "metrics", name + ".py"))
            readers[name] = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(readers[name])
        from benchmark.lib import kernel_costs, peaks
    finally:
        sys.path.remove(root)
    lines = [line.strip().removeprefix("ROOT ")
             for line in attention_block_hlo.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    # one millisecond an event, back to back
    ops = [(line, i * 1e-3, (i + 1) * 1e-3) for i, line in enumerate(lines)]
    v5e = peaks.peaks_for("TPU v5 lite")
    art = {"kind": "train", "peaks": v5e,
           "dims": {"d": HEADS * HEAD_DIM, "heads": HEADS},
           "job": {"batch_size": CELL_ROWS, "block_size": BLOCK},
           "trace": {"planes": {"devices": {0: {"ops": ops}}, "spans": []},
                     "w0": 0.0, "w1": 1.0}}
    cost = kernel_costs.flash_attention(CELL_ROWS, HEADS, BLOCK, HEAD_DIM, 2)
    least = sum(kernel_costs.roofline_seconds(cost[k], v5e)[0]
                for k in ("fwd", "bwd"))
    assert len(ops) == 3
    assert readers["penroz_flash_roofline"].read(art) == pytest.approx(
        100.0 * least / 3e-3)
    assert readers["flash_roofline_pct"].read(art) is None


def test_flash_kernels_are_where_the_benchmark_looks_for_them(chip):
    """Pins the ``(B, H, T, D)`` entry, which sequence parallelism and
    modules that fall back still call (the cell's own kernels are pinned by
    ``test_cell_kernels_are_where_the_benchmark_looks_for_them``).
    ``benchmark/metrics/flash_roofline_pct.py`` finds these in a device
    trace by what their HLO instructions look like: a custom call under a
    ``jvp`` name stack whose results are the output then the logsumexp,
    and custom call(s) under ``transpose(jvp`` with a result of the
    output's shape; the calls' own names (``penroz_flash_fwd`` /
    ``penroz_flash_bwd``) follow that prefix.  A change to this entry that
    would blind that reader fails here, on the CPU, at the cell's shape."""
    import re
    from penroz_tpu.ops import attention as A
    shape = (CELL_ROWS, HEADS, BLOCK, HEAD_DIM)
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    def loss(q, k, v):
        return A.causal_attention(q, k, v, platform="tpu").astype(
            jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv).compile().as_text()
    calls = _custom_calls(hlo)
    # the reader's own patterns (kernel_time: search on name and result)
    out = r"bf16\[12,12,1024,64\]"
    lse = r"f32\[12,12,1024,1\]"
    fwd = [c for c in calls if re.search(r"^%jvp_", c[0])
           and re.search(out + ".*" + lse, c[1])]
    bwd = [c for c in calls if re.search(r"^%transpose_jvp_", c[0])
           and re.search(out, c[1])]
    assert len(fwd) == 1 and "penroz_flash_fwd" in fwd[0][0], calls
    assert 1 <= len(bwd) <= 2, calls
    assert all("penroz_flash_bwd" in c[0] for c in bwd), calls
    assert len(calls) == len(fwd) + len(bwd), calls


def test_looped_stack_compiles_and_its_kernels_are_where_the_benchmark_looks(
        chip):
    """One layer run twice with shared weights at the looped cell's widths
    (``ouro-train-4k-loop4``: micro-batch 2 x 4096, d 2048, 16 heads of 128,
    SwiGLU 5632, vocabulary 49152, bf16), the exit loss and its gradient,
    compiled for a v5e: every application's flash forward appears once
    (the loop's recomputation keeps ``o`` and the logsumexp by name and runs
    the matmuls around them again, not the kernel: twice before PR 40), its
    backward once and in one pass (Mosaic takes the limit the plan asks for
    at T = 4096, D = 128: two kernels before PR 42), each exit's
    cross-entropy forward once and backward once, all under their names —
    and the benchmark's two readers, loaded from their files, find them in a
    trace made of this program's instructions."""
    import importlib.util
    import re
    import sys
    from penroz_tpu.models import dsl, presets
    from penroz_tpu.models.model import CompiledArch
    steps, rows, block = 2, 2, 4096
    arch = CompiledArch.get(presets.ouro_custom(
        d=2048, heads=16, head_dim=128, intermediate=5632, depth=1,
        steps=steps, vocab=49152))
    shapes, _ = jax.eval_shape(
        lambda: dsl.init_module_params(arch.mods, seed=0))
    params = {k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16, sharding=chip)
              for k, v in shapes.items()}
    x = jax.ShapeDtypeStruct((rows, block), jnp.int32, sharding=chip)

    def loss(p, x, y):
        _, cost, _, _ = arch.forward(p, {}, x, y, training=True,
                                     skip_softmax=True,
                                     compute_dtype=jnp.bfloat16,
                                     platform="tpu")
        return cost

    hlo = jax.jit(jax.grad(loss)).lower(params, x, x).compile().as_text()
    calls = _custom_calls(hlo)
    count = lambda needle: sum(needle in name for name, _ in calls)
    assert count("penroz_flash_fwd") == steps, calls
    # ``penroz_flash_bwd`` is a prefix of the δ kernel's name
    assert count("penroz_flash_bwd_delta") == steps, calls
    assert count("penroz_flash_bwd") == 2 * steps, calls
    assert not count("penroz_flash_bwd_dq") and \
        not count("penroz_flash_bwd_dkv"), calls
    assert count("penroz_ce_fwd") == steps, calls
    assert count("penroz_ce_bwd") == steps, calls
    # the rotation: forward, again in the backward's recomputation, and
    # turned back over the cotangents (PR 49)
    assert count("penroz_rope") == 3 * steps, calls
    assert len(calls) == 8 * steps, calls
    # attention stays in the model's layout at D = 128, RoPE included: no
    # head-major array, no head-split view of q, k or their halves
    assert "bf16[2,16,4096,128]" not in hlo
    assert not re.findall(r"\[[\d,]*,16,(?:128|64)\]", hlo)
    assert "pad_maximum_fusion" not in hlo

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        readers = {}
        for name in ("penroz_flash_roofline.useful", "penroz_ce_roofline"):
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + name.replace(".", "_"),
                os.path.join(root, "benchmark", "metrics", name + ".py"))
            readers[name] = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(readers[name])
        from benchmark.lib import kernel_costs, looped_costs, peaks
    finally:
        sys.path.remove(root)
    lines = [line.strip().removeprefix("ROOT ") for line in hlo.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    ops = [(line, i * 1e-3, (i + 1) * 1e-3) for i, line in enumerate(lines)]
    v5e = peaks.peaks_for("TPU v5 lite")
    art = {"kind": "train", "peaks": v5e,
           "dims": {"d": 2048, "heads": 16, "head_dim": 128, "vocab": 49152},
           "job": {"batch_size": rows, "block_size": block},
           "trace": {"planes": {"devices": {0: {"ops": ops}}, "spans": []},
                     "w0": 0.0, "w1": 1.0}}
    least = lambda cost: kernel_costs.roofline_seconds(cost, v5e)[0]
    flash = kernel_costs.flash_attention(rows, 16, block, 128, 2)
    assert readers["penroz_flash_roofline.useful"].read(art) == pytest.approx(
        100.0 * steps * (least(flash["fwd"]) + least(flash["bwd"]))
        / (3 * steps * 1e-3))
    ce = looped_costs.cross_entropy(rows * block, 49152, 2)
    assert readers["penroz_ce_roofline"].read(art) == pytest.approx(
        100.0 * steps * (least(ce["fwd"]) + least(ce["bwd"]))
        / (2 * steps * 1e-3))


ROPE_VIEWS = ([2, 4096, 16, 128], [2, 4096, 16, 64], [1024, 8, 16, 128],
              [4096, 16, 128], [512, 8, 16, 128])


def test_looped_cells_attention_turns_q_and_k_where_they_lie(chip):
    """The looped cell's attention (``ouro-train-4k-loop4``: the fused
    ``(2, 4096, 6144)`` projection of 16 + 16 + 16 heads of 128, bf16, RoPE
    at θ = 1e6), value and gradient, compiled for a v5e.  The rotation is
    two ``penroz_rope`` calls (q, k turned and v carried across to the flash
    entry's three arrays; their cotangents turned back into the
    projection's), and nothing of XLA's rotation is left: no array in a
    head-split shape (``(…, 16, 128)``, the halves' ``(…, 16, 64)``, the
    compiler's tiles of them), no ``pad_maximum_fusion`` (``rotate_half``'s
    concatenate), and by ``scripts/epoch_hlo_groups.py --touching``'s rule
    no instruction touches one, where the parent's epoch program moved 88.9
    GB an optimizer step through them (PERF.md §6, PR 49).  The plan line an
    operator reads is pinned with it."""
    import re
    from penroz_tpu.ops import modules as M
    groups = _epoch_hlo_groups()
    attn = M.CausalSelfAttention(num_heads=16, rope_theta=1e6)
    attn.bind("attn")
    plan = attn.rope_plan(2, 4096, 128, True, 2)
    assert " ".join(f"{k}={v}" for k, v in plan.items()) == (
        "path=kernel heads=16 kv_heads=16 D=128 T=4096 rotary_dim=128 "
        "bytes=134217728")
    assert attn.rope_plan(2, 4096, 128, False, 2)["path"] == "xla"
    qkv = jax.ShapeDtypeStruct((2, 4096, 6144), jnp.bfloat16, sharding=chip)

    def loss(qkv):
        out = attn.apply(qkv, M.Ctx({}, platform="tpu"))
        return out.astype(jnp.float32).sum()

    hlo = jax.jit(jax.value_and_grad(loss)).lower(qkv).compile().as_text()
    calls = _custom_calls(hlo)
    turned = [types for name, types in calls if "penroz_rope" in name]
    assert len(turned) == 2 and len(calls) == 5, calls
    # forward: q, k, v apart; backward: the fused projection's cotangent
    assert len(re.findall(r"bf16\[2,4096,2048\]", turned[0])) == 3, calls
    assert re.match(r"bf16\[2,4096,6144\]", turned[1]), calls
    assert not re.findall(r"\[[\d,]*,16,(?:128|64)\]", hlo)
    assert "pad_maximum_fusion" not in hlo and " transpose(" not in hlo
    comps = groups.parse_computations(hlo)
    rows = []
    groups.walk(comps, groups.result_types(comps), "__entry__", 1, rows,
                [list(dims) for dims in ROPE_VIEWS])
    assert rows == []


def test_a_bare_checkpoint_still_runs_the_flash_forward_twice(chip):
    """The names ``_flash_fwd_rule`` gives its results are inert outside a
    policy that asks for them: the cell's attention block (not looped) under
    a ``jax.checkpoint`` with no policy, as ``models/model.py`` and
    ``parallel/pipeline.py`` wrap theirs, compiles to the forward kernel
    twice (once recomputed) and the backward as ever; with the loop's
    policy, once."""
    from penroz_tpu.ops import modules as M
    attend, qkv, w = _attention_block(chip)

    def block(qkv, w):
        # tanh: something for the checkpoint to recompute around the kernel
        return jnp.tanh(attend(qkv)) @ w

    def forwards(policy):
        # squared: the cotangent reads the primal, so its forward stays
        loss = lambda qkv, w: jnp.square(jax.checkpoint(
            block, policy=policy)(qkv, w).astype(jnp.float32)).sum()
        calls = _custom_calls(jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            qkv, w).compile().as_text())
        count = lambda needle: sum(needle in name for name, _ in calls)
        assert count("penroz_flash_bwd") == 2, calls    # δ and the one pass
        assert len(calls) == 2 + count("penroz_flash_fwd"), calls
        return count("penroz_flash_fwd")

    assert forwards(None) == 2
    assert forwards(jax.checkpoint_policies.save_only_these_names(
        *M._kept_names())) == 1


# ---------------------------------------------------------------------------
# the share cell (``laguna-train-8k-ep32share``): the grouped products and
# one block of each attention kind, at the cell's shapes
# ---------------------------------------------------------------------------

MOE_ROWS, MOE_D, MOE_H, MOE_HELD, MOE_TILE = 8192, 3072, 1024, 8, 128


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_moe_grouped_products_compile_for_v5e(chip, which):
    """The dropless layer's grouped products over a round's row buffer of
    the cell (8192 rows of 3072, 8 held experts of width 1024, bf16, tiles
    of 128 rows): ``penroz_moe_gmm_fwd`` for gate/up and for down (the
    contraction 3072 and 1024 long), and in the gradient ``_bwd_dx`` and
    ``_bwd_dw`` for both, all under the names the benchmark's reader looks
    for."""
    from penroz_tpu.ops.pallas import moe_gmm
    shapes = [((MOE_ROWS, MOE_D), jnp.bfloat16),
              ((MOE_HELD, MOE_H, MOE_D), jnp.bfloat16),
              ((MOE_HELD, MOE_D, MOE_H), jnp.bfloat16),
              ((MOE_ROWS // MOE_TILE,), jnp.int32)]

    def forward(x, up, down, tiles):
        product = lambda a, w: moe_gmm.grouped_matmul_kernel(
            a, w, tiles, row_tile=MOE_TILE)
        return product(product(x, up), down)

    fn = forward if which == "fwd" else jax.grad(
        lambda *a: forward(*a).astype(jnp.float32).sum(), (0, 1, 2))
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    calls = _custom_calls(jax.jit(fn).lower(*args).compile().as_text())
    count = lambda needle: sum(needle in name for name, _ in calls)
    # the gradient of a sum needs the first product's result only
    assert count("penroz_moe_gmm_fwd") == (2 if which == "fwd" else 1), calls
    assert count("penroz_moe_gmm_bwd_dx") == (2 if which == "bwd" else 0)
    assert count("penroz_moe_gmm_bwd_dw") == (2 if which == "bwd" else 0)


@pytest.mark.parametrize("kind,heads", [
    ("sliding_attention", 9), ("full_attention", 6)])
def test_laguna_block_compiles_for_v5e_at_the_cells_shapes(chip, kind, heads):
    """One sparse block of ``presets.laguna_custom`` as the cell holds it
    (1 x 8192 tokens, d 3072, 9 or 6 query heads of 128 on one K/V head, a
    window of 512 or YaRN on half a head, per-head gate, 8 of 256 experts
    top-10 beside a shared one, bf16), loss and gradient, compiled for a
    v5e: attention stays in the model's layout with grouped queries
    (``penroz_flash_fwd`` once, the one-pass ``penroz_flash_bwd``), and the
    dropless layer's grouped calls are there: the forward's three, and in
    each of the backward's two branches (one round, as the products made
    its gradients; several, summed in float32; one of them runs) those
    three again by the routed path's recomputation with three of each
    gradient.  The layer moves no row and no index by a scatter: its rows
    come back to their tokens through ``penroz_moe_combine`` (``y`` in the
    forward, ``dx`` in either branch of the backward), and what scatters
    the program keeps are the embedding's gradient into the vocabulary's
    table and the grouped products' one flag a tile."""
    import math
    import re
    from penroz_tpu.models import dsl, presets
    from penroz_tpu.models.model import CompiledArch
    rope = {"full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}}
    arch = CompiledArch.get(presets.laguna_custom(
        d=3072, head_dim=128, layer_types=[kind], heads_per_layer=[heads],
        kv_heads=1, mlp_layer_types=["sparse"], intermediate=12288,
        num_experts=256, experts_held=8, top_k=10, moe_intermediate=1024,
        shared_intermediate=1024, vocab=12544, window=512, rope=rope,
        routed_scale=2.5, published_layers=48))
    shapes, _ = jax.eval_shape(
        lambda: dsl.init_module_params(arch.mods, seed=0))
    params = {k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16, sharding=chip)
              for k, v in shapes.items()}
    x = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=chip)

    def loss(p, x, y):
        _, cost, _, _ = arch.forward(p, {}, x, y, training=True,
                                     skip_softmax=True,
                                     compute_dtype=jnp.bfloat16,
                                     platform="tpu")
        return cost

    hlo = jax.jit(jax.grad(loss)).lower(params, x, x).compile().as_text()
    calls = _custom_calls(hlo)
    count = lambda needle: sum(needle in name for name, _ in calls)
    assert count("penroz_flash_fwd") == 1, calls
    assert count("penroz_flash_bwd_dq") == 0, calls     # one pass at 8192
    assert count("penroz_moe_gmm_fwd") == 3 + 2 * 3, calls
    assert count("penroz_moe_gmm_bwd_dx") == 2 * 3, calls
    assert count("penroz_moe_gmm_bwd_dw") == 2 * 3, calls
    assert count("penroz_moe_combine") == 3, calls
    # the sliding layers rotate whole heads: q and k turned where they lie,
    # and turned back over the cotangents; the full layers rotate half a
    # head and keep apply_rope (PR 49)
    assert count("penroz_rope") == (2 if kind == "sliding_attention" else 0)
    # no scatter of rows of the model's width but the embedding's own, and
    # none as long as the (token, choice) pairs or the rows' bound
    scatters = [tuple(int(n) for n in dims.split(","))
                for dims in re.findall(r" = \w+\[([\d,]+)\]\S* scatter\(", hlo)]
    assert scatters, hlo[:2000]
    assert [s for s in scatters if s[-1] == 3072] == [(12544, 3072)], scatters
    assert all(math.prod(s) < 8192 for s in scatters if s[-1] != 3072), \
        scatters
    # attention never leaves (B, T, H·D)
    assert f"bf16[1,{heads},8192,128]" not in hlo


# -- unlike score and value widths: latent attention's flash calls ------------

def _mla_shapes(heads):
    return (1, heads, 4096, 192), (1, heads, 4096, 128)     # q and k; v


def _flash_mla(backward, heads):
    from penroz_tpu.ops.pallas import flash_attention as fa
    qk, v = _mla_shapes(heads)
    shapes = [(qk, jnp.bfloat16), (qk, jnp.bfloat16), (v, jnp.bfloat16)]
    attend = lambda q, k, v: fa.flash_attention(q, k, v, scale=0.14468)
    loss = lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum()
    return (jax.grad(loss, argnums=(0, 1, 2)) if backward else attend), shapes


@pytest.mark.parametrize("heads", [8, 32], ids=["held8", "published32"])
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
def test_flash_compiles_for_v5e_at_192_wide_scores_over_128_wide_values(
        chip, backward, heads):
    """The new cell's attention call (``xing-train-4k-ep8share``: 1 x 8 held
    heads x 4096, q and k 192 wide, v and o 128 wide, bf16; and the
    published 32 heads), unpadded: Mosaic takes the 192-lane blocks as they
    are, the forward with K/V resident, the backward in one pass under the
    limit its plan asks for, under the names the benchmark reads; the
    results are as wide as their operands."""
    fn, shapes = _flash_mla(backward, heads)
    qk_shape, v_shape = _mla_shapes(heads)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    calls = _custom_calls(compiled.as_text())
    count = lambda needle: sum(needle in name for name, _ in calls)
    assert count("penroz_flash_fwd") == 1, calls
    if not backward:
        assert len(calls) == 1, calls
        assert jax.eval_shape(fn, *args).shape == v_shape
        return
    # one pass, and no kernel for δ in this layout (an XLA reduction)
    assert count("penroz_flash_bwd") == 1 and len(calls) == 2, calls
    assert not count("penroz_flash_bwd_dq"), calls
    got = jax.eval_shape(fn, *args)
    assert [g.shape for g in got] == [qk_shape, qk_shape, v_shape]


def test_accepted_cells_flash_plans_read_as_they_did():
    """The plan lines of the three accepted cells' attention (GPT-2's fused
    projection at D = 64, the looped cell's one pass at D = 128, the share
    cell's grouped queries at T = 8192 with and without a window), letter
    for letter as before the kernels learnt a value width: PERF.md §3 quotes
    them and an operator compares a job's log with it."""
    from penroz_tpu.ops.pallas import flash_attention as fa
    same = ("bq=512 bk=512 bwd_bq=512 bwd_bk=512 diag_grain=256 "
            "bwd_diag_grain=128 ")
    assert fa.plan_flash(1024, 1024, 64, 2, heads=12, layout="btd",
                         fused_qkv=True).describe() == same + (
        "computed_over_live=1.250 bwd_computed_over_live=1.125 resident "
        "q_rows=1024 fused_bwd bwd_vmem_mib=10.9 heads_per_step=2 "
        "layout=btd heads_per_block=2 fused_qkv")
    assert fa.plan_flash(4096, 4096, 128, 2, heads=16,
                         layout="btd").describe() == same + (
        "computed_over_live=1.062 bwd_computed_over_live=1.031 resident "
        "q_rows=1024 fused_bwd bwd_vmem_mib=24.1 heads_per_step=1 "
        "layout=btd heads_per_block=1")
    assert fa.plan_flash(8192, 8192, 128, 2, heads=6, group=6,
                         layout="btd").describe() == same + (
        "computed_over_live=1.031 bwd_computed_over_live=1.016 chunked "
        "q_rows=512 fused_bwd bwd_vmem_mib=41.8 heads_per_step=1 "
        "layout=btd heads_per_block=1")
    assert fa.plan_flash(8192, 8192, 128, 2, window=512, heads=9, group=9,
                         layout="btd").describe() == same + (
        "computed_over_live=1.688 bwd_computed_over_live=1.562 chunked "
        "q_rows=512 fused_bwd bwd_vmem_mib=41.8 heads_per_step=1 "
        "layout=btd heads_per_block=1")


# -- the stream mixing's passes over its state ---------------------------------

HC_STATE = ([1, 4096, 4, 3584], [1, 4, 4096, 3584], [4096, 14336])


def test_stream_mixing_moves_its_state_in_kernel_passes_alone(chip):
    """Three sub-blocks of ``xing4.0-29b-a4b-ep8-5l``'s residual (4 streams
    of 3584 over 1 x 4096 tokens, bf16: one that expands, the dense SwiGLU
    of 9216 between whole states, one that reduces), value and gradient in
    training, so each under ``_recomputed``, compiled for a v5e.  The state
    is read and written by ``penroz_hc_mix`` calls alone (a sub-block: the
    statistics, ``x_in`` and ``X'`` forward, the first two again in the
    backward's recomputation, three backward passes, ``dPhi`` inside the
    last), the first sub-block's broadcast and its sum apart: no ``copy`` of
    its shape, no float32 array of its size anywhere in the program, and by
    ``scripts/epoch_hlo_groups.py``'s rule (operands + results of every
    instruction that touches it) under 6.2 GB for the three (5.59 read),
    where the formulas written out plainly moved 4.2 GB a sub-block
    (PERF.md §6, PR 48).  The plan line an operator reads beside ``hc
    plan:`` is pinned with it."""
    import re
    from penroz_tpu.ops import modules as M
    groups = _epoch_hlo_groups()
    d, n, T = 3584, 4, 4096
    linear = lambda: M.Sequential(M.RMSNorm(d), M.Linear(d, d, bias=False))
    subs = [M.HyperConnected(d, linear(), streams=n, expand=True),
            M.HyperConnected(d, M.Sequential(
                M.RMSNorm(d), M.GatedMLP(d, 9216, activation="silu")),
                streams=n),
            M.HyperConnected(d, linear(), streams=n, reduce=True)]
    for i, sub in enumerate(subs):
        sub.bind(f"h{i}")
    plan = subs[1].mix_plan(1, T, True, 2)
    assert " ".join(f"{k}={v}" for k, v in plan.items()) == (
        "path=kernel streams=4 features=3584 tokens=4096 bytes=528482304")
    assert subs[1].mix_plan(1, T, False, 2)["path"] == "fused"
    assert subs[1].mix_plan(1, T - 8, True, 2)["path"] == "fused"
    from penroz_tpu.models import dsl
    shapes, _ = jax.eval_shape(lambda: dsl.init_module_params(subs, seed=0))
    params = {k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16, sharding=chip)
              for k, v in shapes.items()}
    x = jax.ShapeDtypeStruct((1, T, d), jnp.bfloat16, sharding=chip)

    def loss(params, x):
        ctx = M.Ctx(params, training=True, rng=jax.random.key(0),
                    compute_dtype=jnp.bfloat16, platform="tpu")
        for sub in subs:
            x = sub.apply(x, ctx)
        return x.astype(jnp.float32).sum()

    hlo = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        params, x).compile().as_text()
    calls = [name for name, _ in _custom_calls(hlo)
             if "penroz_hc_mix" in name]
    assert len(calls) == 3 * 8, calls
    state = "|".join(",".join(map(str, dims)) for dims in HC_STATE)
    assert not re.findall(rf" = bf16\[({state})\]\S* copy\(", hlo)
    assert not re.findall(rf"f32\[({state}|4,4096,3584|4096,4,3584)\]", hlo)
    comps = groups.parse_computations(hlo)
    rows = []
    groups.walk(comps, groups.result_types(comps), "__entry__", 1, rows,
                [list(dims) for dims in HC_STATE])
    assert {g for g, *_ in rows} <= {
        "penroz_hc_mix", "jvp_penroz_hc_mix_", "transpose_jvp_penroz_hc_mix__",
        "broadcast", "reduce_sum"}, sorted({g for g, *_ in rows})
    moved = sum(runs * nbytes for _, runs, _, nbytes in rows)
    assert 4.5e9 < moved < 6.2e9, moved
