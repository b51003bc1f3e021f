"""Layer-level unit tests: constructor/param shapes + forward shapes
(mirrors the reference's test strategy: test_neural_net_layers.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from penroz_tpu.ops import modules as M
from penroz_tpu.ops.kv_cache import KVState


def apply(mod, x, params=None, buffers=None, **ctx_kw):
    mod.bind(mod.prefix or "layer")
    if params is None:
        params = {}
        buffers = {}
        for sub in mod.walk():
            params.update(sub.init(jax.random.key(0)))
            buffers.update(sub.init_buffers())
    ctx = M.Ctx(params, buffers, **ctx_kw)
    return mod.apply(jnp.asarray(x), ctx), ctx


@pytest.mark.parametrize("mod,param_count", [
    (M.Embedding(10, 4), 40),
    (M.Linear(8, 3), 27),
    (M.Linear(8, 3, bias=False), 24),
    (M.LayerNorm(6), 12),
    (M.BatchNorm1d(6), 12),
    (M.RMSNorm(6), 6),
    (M.GatedMLP(4, 8), 3 * 32),
    (M.ScaledEmbedding(10, 4, scale=2.0), 40),
    (M.PositionEmbedding(10, 4), 40),
])
def test_param_counts(mod, param_count):
    mod.bind("m")
    params = {}
    for sub in mod.walk():
        params.update(sub.init(jax.random.key(0)))
    total = sum(int(np.prod(p.shape)) for p in params.values())
    assert total == param_count


def test_linear_forward_shape():
    out, _ = apply(M.Linear(8, 3), np.ones((5, 8), np.float32))
    assert out.shape == (5, 3)


def test_embedding_forward():
    out, _ = apply(M.Embedding(10, 4), np.array([[1, 2, 3]]))
    assert out.shape == (1, 3, 4)


def test_scaled_embedding_scales():
    mod = M.ScaledEmbedding(10, 4, scale=3.0)
    mod.bind("m")
    params = mod.init(jax.random.key(0))
    ctx = M.Ctx(params)
    base = jnp.take(params["m.weight"], jnp.array([1]), axis=0)
    out = mod.apply(jnp.array([1]), ctx)
    np.testing.assert_allclose(out, base * 3.0, rtol=1e-6)


def test_position_embedding_offset():
    mod = M.PositionEmbedding(10, 4)
    mod.bind("m")
    params = mod.init(jax.random.key(0))
    x = jnp.zeros((1, 3), jnp.int32)
    out0 = mod.apply(x, M.Ctx(params))
    out2 = mod.apply(x, M.Ctx(params, pos_offset=jnp.asarray(2)))
    np.testing.assert_allclose(out0[2:], out2[:1], rtol=1e-6)
    assert out2.shape == (3, 4)


def test_softmax_on_last():
    out, _ = apply(M.SoftmaxOnLast(dim=-1), np.random.randn(2, 5, 7).astype(np.float32))
    assert out.shape == (2, 7)
    np.testing.assert_allclose(np.asarray(out).sum(-1), np.ones(2), rtol=1e-5)


def test_rmsnorm_fp32_internals():
    x = (np.random.randn(2, 8) * 10).astype(np.float32)
    out, _ = apply(M.RMSNorm(8), x)
    rms = np.sqrt((x.astype(np.float64) ** 2).mean(-1) + 1e-6)
    np.testing.assert_allclose(np.asarray(out), x / rms[:, None], rtol=1e-4)


def test_batchnorm_train_vs_eval():
    mod = M.BatchNorm1d(4)
    x = np.random.randn(16, 4).astype(np.float32) * 3 + 1
    out, ctx = apply(mod, x, training=True)
    assert out.shape == x.shape
    np.testing.assert_allclose(np.asarray(out).mean(0), np.zeros(4), atol=1e-4)
    assert "layer.running_mean" in ctx.buffer_updates
    assert int(ctx.buffer_updates["layer.num_batches_tracked"]) == 1
    # eval mode uses running stats — with fresh buffers output is just x-ish
    out_eval, ctx2 = apply(mod, x, training=False)
    assert not ctx2.buffer_updates


def test_dropout_active_only_in_training():
    mod = M.Dropout(0.5)
    x = np.ones((64, 64), np.float32)
    out_eval, _ = apply(mod, x, training=False)
    np.testing.assert_array_equal(np.asarray(out_eval), x)
    out_train, _ = apply(mod, x, training=True, rng=jax.random.key(0))
    zeros = float((np.asarray(out_train) == 0).mean())
    assert 0.3 < zeros < 0.7


def test_residual_and_summation():
    lin = M.Linear(4, 4)
    res = M.ResidualConnection(lin)
    out, ctx = apply(res, np.ones((2, 4), np.float32))
    inner = lin.apply(jnp.ones((2, 4)), ctx)
    np.testing.assert_allclose(np.asarray(out), 1 + np.asarray(inner), rtol=1e-5)


@pytest.mark.parametrize("num_heads,num_kv_heads,rope", [
    (4, None, None),
    (4, 2, None),
    (4, 1, 10000.0),
    (4, 4, 10000.0),
])
def test_attention_shapes(num_heads, num_kv_heads, rope):
    head_dim = 8
    kvh = num_kv_heads or num_heads
    total = (num_heads + 2 * kvh) * head_dim
    mod = M.CausalSelfAttention(num_heads=num_heads, num_kv_heads=num_kv_heads,
                                rope_theta=rope, head_dim=head_dim)
    x = np.random.randn(2, 6, total).astype(np.float32)
    out, _ = apply(mod, x)
    assert out.shape == (2, 6, num_heads * head_dim)


def test_attention_causality():
    """Changing a future token must not affect earlier outputs."""
    mod = M.CausalSelfAttention(num_heads=2)
    x = np.random.randn(1, 5, 3 * 16).astype(np.float32)
    out1, _ = apply(mod, x)
    x2 = x.copy()
    x2[0, -1] += 100.0
    out2, _ = apply(mod, x2)
    np.testing.assert_allclose(np.asarray(out1)[0, :4], np.asarray(out2)[0, :4],
                               atol=1e-5)


def test_attention_cached_matches_uncached():
    """Incremental decode through KVState == full causal attention."""
    mod = M.CausalSelfAttention(num_heads=2, num_kv_heads=1, rope_theta=100.0)
    mod.bind("m")
    head_dim = 8
    total = (2 + 2 * 1) * head_dim
    x = np.random.randn(1, 6, total).astype(np.float32)
    full, _ = apply(mod, x)

    kv = KVState.create([(1, head_dim)], batch=1, max_len=8)
    outs = []
    for t in range(6):
        ctx = M.Ctx({}, kv=kv)
        step = mod.apply(jnp.asarray(x[:, t:t + 1]), ctx)
        kv = ctx.kv.advanced(1)
        outs.append(np.asarray(step))
    incremental = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), incremental, atol=1e-4)


@pytest.mark.parametrize("post_norm_on_residual", [True, False])
def test_transformer_block_variants(post_norm_on_residual):
    d = 16
    blk = M.TransformerBlock(
        attn_block=M.Sequential(M.RMSNorm(d), M.Linear(d, 3 * d, bias=False),
                                M.CausalSelfAttention(num_heads=2),
                                M.Linear(d, d, bias=False)),
        mlp_block=M.Sequential(M.RMSNorm(d), M.GatedMLP(d, 2 * d)),
        post_attn_norm=M.RMSNorm(d), post_mlp_norm=M.RMSNorm(d),
        post_norm_on_residual=post_norm_on_residual)
    out, _ = apply(blk, np.random.randn(2, 4, d).astype(np.float32))
    assert out.shape == (2, 4, d)


def test_two_block_gpt_stack(toy_gpt_layers):
    from penroz_tpu.models.dsl import Mapper
    mapper = Mapper(toy_gpt_layers, {"sgd": {"lr": 0.1}})
    mods = mapper.to_modules()
    params, buffers = mapper.init_params(mods, seed=0)
    ctx = M.Ctx(params, buffers)
    h = jnp.asarray(np.random.randint(0, 64, (2, 16)))
    for mod in mods:
        h = mod.apply(h, ctx)
    assert h.shape == (2, 64)
    np.testing.assert_allclose(np.asarray(h).sum(-1), np.ones(2), rtol=1e-4)


def _hints():
    """One device (the scatter) and a mesh (the one-hot scan): the two
    placements modules._gather_rows_bwd tells apart."""
    from penroz_tpu.ops.attention import Placement
    from penroz_tpu.parallel import mesh as mesh_lib
    return [None, Placement("tpu", mesh_lib.make_mesh(jax.devices()[:2]))]


@pytest.mark.parametrize("meshed", [False, True])
def test_gather_rows_matmul_backward_matches_scatter(meshed):
    """The TPU embedding backward (fp32 scatter on one device, chunked
    one-hotᵀ@g matmul under a mesh: modules._gather_rows_bwd) must equal
    jnp.take's native scatter-add VJP — including repeated ids,
    non-chunk-multiple counts, and 2-D id arrays."""
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(17, 8)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 17, (3, 5)), jnp.int32)  # repeats likely
    cot = jnp.asarray(rng.normal(size=(3, 5, 8)), jnp.float32)
    hint = _hints()[meshed]

    def via_custom(t):
        return (M._gather_rows(t, ids, 17, "float32", hint) * cot).sum()

    def via_take(t):
        return (jnp.take(t, ids, axis=0) * cot).sum()

    g_custom = jax.grad(via_custom)(table)
    g_take = jax.grad(via_take)(table)
    np.testing.assert_allclose(np.asarray(g_custom), np.asarray(g_take),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("meshed", [False, True])
def test_gather_rows_backward_chunking(meshed):
    """Id counts above the scan chunk exercise padding + accumulation."""
    rng = np.random.default_rng(1)
    n = M._GATHER_BWD_CHUNK + 37  # forces pad + 2 scan steps
    table = jnp.asarray(rng.normal(size=(23, 4)), jnp.bfloat16)
    ids = jnp.asarray(rng.integers(0, 23, (n,)), jnp.int32)
    cot = jnp.asarray(rng.normal(size=(n, 4)), jnp.bfloat16)
    hint = _hints()[meshed]

    g = jax.grad(lambda t: (M._gather_rows(t, ids, 23, "bfloat16", hint)
                            * cot).astype(jnp.float32).sum())(table)
    # fp32 oracle: the bf16 scatter-add VJP itself drifts (per-add rounding);
    # both paths accumulate in fp32, so compare against exact math.
    want_f32 = jax.grad(
        lambda t: (jnp.take(t, ids, axis=0)
                   * cot.astype(jnp.float32)).sum())(
        table.astype(jnp.float32))
    assert g.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(want_f32, np.float32),
                               rtol=0.02, atol=0.05)


def test_head_rmsnorm_bf16_weight_order_per_family():
    """qk-norm weight-multiply order is per-family: OLMo-2
    (qk_norm_fp32_weight=True) multiplies the fp32 weight in fp32 with a
    single final downcast; Qwen3 (default) downcasts the normalized
    activations FIRST and multiplies in the storage dtype — each matching
    its HF RMSNorm exactly (Olmo2RMSNorm vs Qwen3RMSNorm cast orders)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 3, 2, 8)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(loc=1.0, size=(8,)), jnp.bfloat16)
    xf = np.asarray(x, np.float32)
    wf = np.asarray(w, np.float32)
    eps = 1e-6
    norm = 1.0 / np.sqrt((xf * xf).mean(-1, keepdims=True) + eps)

    olmo = M.CausalSelfAttention(num_heads=2, head_dim=8, qk_norm=True,
                                 qk_norm_scope="flat",
                                 qk_norm_fp32_weight=True)
    got = olmo._head_rmsnorm(x, w)
    assert got.dtype == jnp.bfloat16
    want = jnp.asarray(xf * norm * wf).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))

    qwen = M.CausalSelfAttention(num_heads=2, head_dim=8, qk_norm=True)
    got = qwen._head_rmsnorm(x, w)
    want = (jnp.asarray(xf * norm).astype(jnp.bfloat16) * w
            ).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
