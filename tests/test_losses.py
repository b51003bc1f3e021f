"""Fused chunked cross-entropy vs the optax fp32 oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from penroz_tpu.ops import losses


def _oracle(logits, targets):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets).mean()


@pytest.mark.parametrize("shape,v,chunk", [
    ((4, 7), 13, 512),        # single chunk, padded rows
    ((2, 1024), 301, 256),    # multiple chunks, padded tail
    ((3, 256), 512, 256),     # exact multiple, no padding
    ((5,), 31, 4),            # 1-D targets, tiny chunk
])
def test_loss_matches_oracle(shape, v, chunk):
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(*shape, v)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, v, shape), jnp.int32)
    got = losses.fused_cross_entropy_mean(logits, targets, chunk)
    want = _oracle(logits, targets)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grad_matches_oracle(dtype):
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(2, 96, 257)), dtype)
    targets = jnp.asarray(rng.integers(0, 257, (2, 96)), jnp.int32)

    got = jax.grad(lambda x: losses.fused_cross_entropy_mean(x, targets, 64))(
        logits)
    want = jax.grad(lambda x: _oracle(x, targets))(logits).astype(dtype)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-6 if dtype == jnp.float32 else 1e-3)


def test_jit_and_value_and_grad():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(8, 33)), jnp.bfloat16)
    targets = jnp.asarray(rng.integers(0, 33, (8,)), jnp.int32)

    @jax.jit
    def f(x):
        return jax.value_and_grad(
            lambda z: losses.fused_cross_entropy_mean(z, targets))(x)

    loss, grad = f(logits)
    want = _oracle(logits, targets)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-2)
    # CE row-gradients sum to ~0 (softmax minus onehot)
    np.testing.assert_allclose(np.asarray(grad, np.float32).sum(), 0.0,
                               atol=1e-2)


@pytest.mark.parametrize("n,v,dtype", [
    (16, 1024, jnp.float32),     # exact block tiling
    (40, 2048 + 512, jnp.bfloat16),  # padded rows + vocab tail chunk
    (300, 1536, jnp.float32),    # rows padded to block_n
])
def test_pallas_kernels_match_jnp(n, v, dtype):
    """Interpret-mode Pallas CE fwd/bwd vs the jnp chunk-scan oracle."""
    from penroz_tpu.ops.pallas import cross_entropy as ce

    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(n, v)) * 3, dtype)
    targets = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)

    lse_k, ll_k = ce.ce_forward(logits, targets, block_n=8, block_v=512,
                                interpret=True)
    lse_j, ll_j = losses._jnp_forward(logits, targets, 64)
    np.testing.assert_allclose(np.asarray(lse_k), np.asarray(lse_j),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ll_k), np.asarray(ll_j),
                               rtol=1e-5, atol=1e-5)

    scale = jnp.asarray(0.37, jnp.float32)
    dx_k = ce.ce_backward(logits, targets, lse_k, scale, block_n=8,
                          block_v=512, interpret=True)
    dx_j = losses._jnp_backward(logits, targets, lse_j, scale, 64)
    assert dx_k.dtype == dtype
    np.testing.assert_allclose(np.asarray(dx_k, np.float32),
                               np.asarray(dx_j, np.float32),
                               rtol=1e-4, atol=1e-5)


def test_under_remat():
    """jax.checkpoint over the custom-vjp loss must still produce grads."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(4, 65)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 65, (4,)), jnp.int32)
    f = jax.checkpoint(
        lambda x: losses.fused_cross_entropy_mean(x, targets, 2))
    grad = jax.grad(f)(logits)
    want = jax.grad(lambda x: _oracle(x, targets))(logits)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("shape,v,dtype", [
    ((16,), 1024, jnp.float32),
    ((3, 40), 640, jnp.bfloat16),    # padded rows, leading dims kept
])
def test_rows_and_per_row_cotangent_match_jnp(shape, v, dtype):
    """The per-token form: ``lse − label logit`` a row out, one cotangent a
    row in, against plain jnp."""
    rng = np.random.default_rng(11)
    logits = jnp.asarray(rng.normal(size=(*shape, v)) * 3, dtype)
    targets = jnp.asarray(rng.integers(0, v, shape), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 2.0, shape), jnp.float32)

    def plain(x):
        logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    rows = losses.fused_cross_entropy_rows(logits, targets, 8)
    assert rows.shape == shape and rows.dtype == jnp.float32
    np.testing.assert_allclose(rows, plain(logits), rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda x: jnp.sum(
        weights * losses.fused_cross_entropy_rows(x, targets, 8)))(logits)
    want = jax.grad(lambda x: jnp.sum(weights * plain(x)))(logits)
    assert got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("n,v", [(40, 2048 + 512), (300, 1536)])
def test_pallas_backward_takes_a_cotangent_a_row(n, v):
    from penroz_tpu.ops.pallas import cross_entropy as ce
    rng = np.random.default_rng(13)
    logits = jnp.asarray(rng.normal(size=(n, v)) * 3, jnp.float32)
    targets = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    scale = jnp.asarray(rng.uniform(-1.0, 1.0, (n, 1)), jnp.float32)
    lse, _ = losses._jnp_forward(logits, targets, 64)
    got = ce.ce_backward(logits, targets, lse, scale, block_n=8, block_v=512,
                         interpret=True)
    want = losses._jnp_backward(logits, targets, lse, scale, 64)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    soft = jax.nn.softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(targets, v)
    np.testing.assert_allclose(want, (soft - onehot) * scale, atol=1e-5)


def test_mean_is_unchanged_to_the_bit():
    """The mean built on the per-token form computes what the fused mean
    computed before it: ``Σ(lse − ll) / N`` forward, ``(softmax − onehot) ·
    ḡ / N`` backward, from the same chunked scan."""
    rng = np.random.default_rng(17)
    n, v = 300, 515
    logits = jnp.asarray(rng.normal(size=(n, v)) * 3, jnp.float32)
    targets = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    gbar = jnp.asarray(0.7, jnp.float32)

    @jax.jit
    def before(x):
        lse, ll = losses._jnp_forward(x, targets, 64)
        return (jnp.sum(lse - ll) / n,
                losses._jnp_backward(x, targets, lse, gbar / n, 64))

    @jax.jit
    def now(x):
        value, pull = jax.vjp(
            lambda z: losses.fused_cross_entropy_mean(z, targets, 64), x)
        return value, pull(gbar)[0]

    for got, want in zip(now(logits), before(logits)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
