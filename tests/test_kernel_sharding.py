"""Pallas kernels under a GSPMD mesh: each dispatch maps the kernel over the
mesh's shards (ops/attention.py::_on_shards) and must equal the jnp oracle.

On the chip a Mosaic call inside a program partitioned over more than one
device is refused outright; nothing on the CPU shows that, so what can be
proven here is the mapping itself — which dims are split over ``data`` and
``model``, which are gathered — with the kernels in interpret mode on four
virtual devices.  tests/test_tpu_compile.py asks the chip's compiler.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from penroz_tpu.ops import attention as A
from penroz_tpu.ops import losses, ssm
from penroz_tpu.parallel import mesh as mesh_lib


@pytest.fixture
def hint(monkeypatch):
    """A ``Placement`` over a data=2 × model=2 mesh, with every kernel
    entry switched to interpret mode (the hint says 'tpu'; this is a CPU)."""
    from penroz_tpu.ops.pallas import (cross_entropy, decode_attention,
                                       flash_attention, paged_attention,
                                       ragged_paged_attention, ssm_scan)
    for mod, name in ((flash_attention, "flash_attention"),
                      (decode_attention, "decode_attention"),
                      (paged_attention, "paged_decode_attention"),
                      (ragged_paged_attention, "ragged_paged_attention"),
                      (cross_entropy, "ce_forward"),
                      (cross_entropy, "ce_backward"),
                      (ssm_scan, "gla_chunked")):
        monkeypatch.setattr(mod, name, functools.partial(
            getattr(mod, name), interpret=True))
    mesh = mesh_lib.make_mesh(jax.devices()[:4], model=2)
    return A.Placement("tpu", mesh)


def _rand(rng, *shape):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


def _put(hint, x, *spec):
    return jax.device_put(x, NamedSharding(hint.mesh, P(*spec)))


def test_flash_mapped_over_data_and_model(hint):
    rng = np.random.default_rng(0)
    q, k, v = (_put(hint, _rand(rng, 4, h, 128, 64), "data", "model")
               for h in (4, 2, 2))

    def loss(fn, q, k, v):
        return (fn(q, k, v) ** 2).sum()

    mapped = functools.partial(A.causal_attention, platform=hint)
    got = jax.jit(jax.value_and_grad(functools.partial(loss, mapped),
                                     argnums=(0, 1, 2)))(q, k, v)
    want = jax.value_and_grad(
        functools.partial(loss, A.causal_attention_reference),
        argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def test_flash_alibi_keeps_heads_whole(hint):
    """ALiBi slopes are a static per-head table the kernel indexes by local
    head id, so heads are gathered — only the batch stays split."""
    rng = np.random.default_rng(1)
    q, k, v = (_put(hint, _rand(rng, 2, 4, 128, 64), "data", "model")
               for _ in range(3))
    slopes = A.alibi_slopes(4)
    got = jax.jit(functools.partial(A.causal_attention, platform=hint,
                                    alibi=slopes))(q, k, v)
    want = A.causal_attention_reference(q, k, v, alibi=slopes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_decode_mapped(hint, quantized):
    rng = np.random.default_rng(2)
    B, Hq, Hkv, S, D = 4, 4, 2, 128, 64
    q = _put(hint, _rand(rng, B, Hq, 1, D), "data", "model")
    kv = [_put(hint, _rand(rng, B, Hkv, S, D), "data", "model")
          for _ in range(2)]
    lengths = jnp.asarray([5, 128, 64, 1], jnp.int32)
    scales = {}
    if quantized:
        kv = [jnp.round(x * 20).astype(jnp.int8) for x in kv]
        scales = {"k_scale": _rand(rng, B, Hkv, S, 1) ** 2 / 20,
                  "v_scale": _rand(rng, B, Hkv, S, 1) ** 2 / 20}
    got = jax.jit(functools.partial(A.cached_attention, platform=hint))(
        q, *kv, 0, lengths, **scales)
    want = A.cached_attention(q, *kv, 0, lengths, platform="cpu", **scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _paged_pool(rng, hint, rows=4, pages_per_seq=4, page=8, heads=2, dim=64):
    pool = [_put(hint, _rand(rng, heads, rows * pages_per_seq * page, dim),
                 "model") for _ in range(2)]
    table = jnp.asarray(rng.permutation(rows * pages_per_seq)
                        .reshape(rows, pages_per_seq).astype(np.int32))
    return pool, table, page


def test_paged_mapped(hint):
    rng = np.random.default_rng(3)
    pool, table, page = _paged_pool(rng, hint)
    q = _put(hint, _rand(rng, 4, 4, 1, 64), "data", "model")
    lengths = jnp.asarray([3, 32, 17, 8], jnp.int32)
    got = jax.jit(functools.partial(A.paged_cached_attention,
                                    page_size=page, platform=hint))(
        q, *pool, table, offset=0, length=lengths)
    want = A.paged_cached_attention(q, *pool, table, page, 0, lengths,
                                    platform="cpu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ragged_mapped(hint):
    """A decode row and a two-block prefill chunk side by side; only the
    heads split (descriptors address block-table rows globally)."""
    rng = np.random.default_rng(4)
    pool, table, page = _paged_pool(rng, hint)
    block_q = 8
    descs = jnp.asarray([[0, 20, 1, 21],      # row 0 decodes at position 20
                         [2, 0, 8, 13],       # row 2 prefills 13 tokens …
                         [2, 8, 5, 13],       # … in two blocks
                         [-1, 0, 0, 0]], jnp.int32)
    q = _put(hint, _rand(rng, 1, 4, 4 * block_q, 64), None, "model")
    got = jax.jit(functools.partial(A.ragged_paged_cached_attention,
                                    page_size=page, platform=hint))(
        q, *pool, table, descs=descs)
    want = A.ragged_paged_attention_reference(q, *pool, table, page, descs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fused_ce_mapped(hint):
    rng = np.random.default_rng(5)
    logits = _put(hint, _rand(rng, 4, 64, 1024), "data")
    targets = _put(hint, jnp.asarray(rng.integers(0, 1024, (4, 64)),
                                     jnp.int32), "data")
    got = jax.jit(jax.value_and_grad(
        lambda x: losses.fused_cross_entropy_mean(x, targets, 512, hint)))(
            logits)
    want = jax.value_and_grad(
        lambda x: losses.fused_cross_entropy_mean(x, targets, 512, "cpu"))(
            logits)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-6)


def test_ssm_scan_mapped(hint):
    rng = np.random.default_rng(6)
    q, k, v = (_put(hint, _rand(rng, 2, 16, 4, 8), "data", None, "model")
               for _ in range(3))
    g = _put(hint, jnp.asarray(rng.uniform(0.05, 0.98, (2, 16, 4)),
                               jnp.float32), "data", None, "model")
    got = jax.jit(functools.partial(ssm.gla_full, platform=hint))(q, k, v, g)
    want = ssm.gla_full_reference(q, k, v, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("axes", [{"data": 4}, {"data": 2, "model": 2}])
def test_embedding_grad_sums_once_over_the_data_axis(axes):
    """The token embedding's backward under a mesh (no Pallas kernel: the
    one-hot scan, partitioned by GSPMD), ids and cotangent rows split over
    ``data``: the one-device table, not a shard's share of it nor ``data``
    times it."""
    from penroz_tpu.ops import modules as M
    mesh = mesh_lib.make_mesh(jax.devices()[:4], model=axes.get("model", 1))
    placed = A.Placement("tpu", mesh)
    rng = np.random.default_rng(7)
    num_rows, rows, T, d = 300, 4, 256, 32
    table = _put(placed, _rand(rng, num_rows, d))
    ids = _put(placed, jnp.asarray(rng.integers(0, num_rows, (rows, T)),
                                   jnp.int32), "data")
    cot = _put(placed, _rand(rng, rows, T, d), "data")

    def grad(platform):
        return jax.jit(jax.grad(lambda t, ids, cot: (M._gather_rows(
            t, ids, num_rows, "float32", platform) * cot).sum()))(
                table, ids, cot)

    want = jax.grad(lambda t: (jnp.take(t, ids, axis=0) * cot).sum())(table)
    for platform in (placed, "tpu"):
        np.testing.assert_allclose(np.asarray(grad(platform)),
                                   np.asarray(want), rtol=1e-5, atol=1e-4)
