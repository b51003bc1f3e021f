"""Attention math tests: RoPE, GQA grouping, cached-vs-causal equivalence,
and the Pallas flash kernel (interpret mode) against the jnp oracle."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from penroz_tpu.ops import attention as A


def _qkv(B=1, Hq=4, Hkv=2, T=8, D=16, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(B, Hq, T, D)).astype(np.float32)
    k = rng.normal(size=(B, Hkv, T, D)).astype(np.float32)
    v = rng.normal(size=(B, Hkv, T, D)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def test_rope_preserves_norm_and_position_zero():
    q, k, _ = _qkv()
    q2, k2 = A.apply_rope(q, k, 10000.0, jnp.asarray(0))
    np.testing.assert_allclose(np.linalg.norm(np.asarray(q2), axis=-1),
                               np.linalg.norm(np.asarray(q), axis=-1),
                               rtol=1e-5)
    # position 0 rotation is identity
    np.testing.assert_allclose(np.asarray(q2)[:, :, 0], np.asarray(q)[:, :, 0],
                               atol=1e-6)


def test_rope_offset_shifts_positions():
    q, k, _ = _qkv(T=4)
    full_q, _ = A.apply_rope(q, k, 100.0, jnp.asarray(0))
    part_q, _ = A.apply_rope(q[:, :, 2:], k[:, :, 2:], 100.0, jnp.asarray(2))
    np.testing.assert_allclose(np.asarray(full_q)[:, :, 2:],
                               np.asarray(part_q), rtol=1e-5)


def test_partial_rope_rotates_only_leading_dims():
    """rotary_dim < D (GPT-NeoX rotary_pct): trailing dims pass through
    untouched, leading dims match a full-rope call at that width."""
    q, k, _ = _qkv(D=16)
    q2, k2 = A.apply_rope(q, k, 10000.0, jnp.asarray(0), rotary_dim=8)
    np.testing.assert_array_equal(np.asarray(q2)[..., 8:],
                                  np.asarray(q)[..., 8:])
    np.testing.assert_array_equal(np.asarray(k2)[..., 8:],
                                  np.asarray(k)[..., 8:])
    q_ref, k_ref = A.apply_rope(q[..., :8], k[..., :8], 10000.0,
                                jnp.asarray(0))
    np.testing.assert_allclose(np.asarray(q2)[..., :8], np.asarray(q_ref),
                               rtol=1e-6)
    # rotary_dim == D is exactly the full rotation
    q_full, _ = A.apply_rope(q, k, 10000.0, jnp.asarray(0))
    q_full2, _ = A.apply_rope(q, k, 10000.0, jnp.asarray(0), rotary_dim=16)
    np.testing.assert_array_equal(np.asarray(q_full), np.asarray(q_full2))


def test_gqa_matches_expanded_heads():
    """Grouped einsum == explicit KV head expansion."""
    q, k, v = _qkv(Hq=4, Hkv=2)
    grouped = A.causal_attention_reference(q, k, v)
    k_exp = jnp.repeat(k, 2, axis=1)
    v_exp = jnp.repeat(v, 2, axis=1)
    expanded = A.causal_attention_reference(q, k_exp, v_exp)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(expanded),
                               atol=1e-5)


def test_cached_attention_prefill_equals_causal():
    q, k, v = _qkv()
    causal = A.causal_attention_reference(q, k, v)
    # prefill into an oversized cache: length == T, padding masked out
    S_max = 16
    pad = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, S_max - t.shape[2]),
                                (0, 0)))
    cached = A.cached_attention(q, pad(k), pad(v), jnp.asarray(0),
                                jnp.asarray(8))
    np.testing.assert_allclose(np.asarray(causal), np.asarray(cached),
                               atol=1e-5)


def test_flash_kernel_matches_reference_interpret():
    """Pallas kernel (interpreter mode) vs jnp oracle, causal + GQA."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    B, Hq, Hkv, T, D = 1, 2, 1, 256, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32))
    out = FA._flash_forward(q, k, v, causal=True, block_q=128, block_k=128,
                            interpret=True)
    ref = A.causal_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_kernel_noncausal_interpret():
    from penroz_tpu.ops.pallas import flash_attention as FA
    B, H, T, D = 1, 1, 128, 64
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    out = FA._flash_forward(q, k, v, causal=False, block_q=128, block_k=128,
                            interpret=True)
    # non-causal oracle: full mask
    qg = A._group_query_heads(q, 1)
    full = A._attend(qg, k, v, jnp.ones((T, T), bool)).reshape(B, H, T, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full), atol=2e-5)


def test_flash_kernel_odd_tail_blocks():
    """T=384 exercises the non-256-divisible tail (regression: dropped tail)."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    B, H, T, D = 1, 1, 384, 64
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    out = FA._flash_forward(q, k, v, causal=True, block_q=256, block_k=256,
                            interpret=True)
    ref = A.causal_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _grad_close(got, want, rel=2e-4):
    for name, a, b in zip("qkv", got, want):
        err = float(jnp.abs(a - b).max())
        scale = max(float(jnp.abs(b).max()), 1.0)
        assert err <= rel * scale, f"d{name}: {err} > {rel} * {scale}"


def test_flash_backward_kernels_match_oracle():
    """The Pallas dq/dkv kernels (interpret) match the jnp oracle's grads."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    B, H, T, D = 1, 2, 256, 64
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    gf = jax.grad(lambda q, k, v: FA.flash_attention(
        q, k, v, True, 128, 128, interpret=True).sum(), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: A.causal_attention_reference(
        q, k, v).sum(), (0, 1, 2))(q, k, v)
    _grad_close(gf, gr)


def test_flash_backward_gqa_group_sum():
    """GQA backward: per-query-head dK/dV fold correctly over the group."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    B, Hq, Hkv, T, D = 2, 4, 2, 256, 64
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32))
    gf = jax.grad(lambda q, k, v: FA.flash_attention(
        q, k, v, True, 128, 128, interpret=True).sum(), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: A.causal_attention_reference(
        q, k, v).sum(), (0, 1, 2))(q, k, v)
    _grad_close(gf, gr)


def test_flash_backward_long_context_t4096():
    """Grad parity vs the oracle at T≥4096 — the
    K-grid-tiled kernels never hold (T, S) scores or full (S, D) K/V in
    VMEM, so long context lowers and matches."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    B, H, T, D = 1, 1, 4096, 64
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    gf = jax.grad(lambda q, k, v: FA.flash_attention(
        q, k, v, True, 512, 512, interpret=True).sum(), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: A.causal_attention_reference(
        q, k, v).sum(), (0, 1, 2))(q, k, v)
    _grad_close(gf, gr)


def _masked_dropout_oracle(q, k, v, rate, seed):
    """Causal attention applying the kernels' exact hash-derived keep-mask
    (flash_attention.dropout_keep_mask_reference) — the fixed-mask oracle."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    HI = jax.lax.Precision.HIGHEST
    B, Hq, T, D = q.shape
    group = Hq // k.shape[1]
    outs = []
    for b in range(B):
        heads = []
        for h in range(Hq):
            s = jnp.matmul(q[b, h], k[b, h // group].T,
                           precision=HI) / (D ** 0.5)
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
            p = jax.nn.softmax(s, -1)
            keep = FA.dropout_keep_mask_reference(seed, b, h, Hq, T, T, rate)
            p = jnp.where(keep, p / (1 - rate), 0.0)
            heads.append(jnp.matmul(p, v[b, h // group], precision=HI))
        outs.append(jnp.stack(heads))
    return jnp.stack(outs)


def test_flash_dropout_matches_fixed_mask_oracle():
    """Kernel dropout == oracle applying the identical hash mask: forward
    exactly, gradients through both backward kernels."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    B, Hq, Hkv, T, D = 2, 4, 2, 256, 64
    rate, seed = 0.3, 1234
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32))
    out = FA._flash_forward(q, k, v, causal=True, block_q=128, block_k=128,
                            dropout_rate=rate, seed=seed, interpret=True)
    ref = _masked_dropout_oracle(q, k, v, rate, seed)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # dropout actually drops (outputs differ from the no-dropout kernel)
    base = FA._flash_forward(q, k, v, causal=True, block_q=128, block_k=128,
                             interpret=True)
    assert float(jnp.abs(out - base).max()) > 0.01
    gk = jax.grad(lambda q, k, v: FA.flash_attention(
        q, k, v, True, 128, 128, dropout_rate=rate, seed=seed,
        interpret=True).sum(), (0, 1, 2))(q, k, v)
    go = jax.grad(lambda q, k, v: _masked_dropout_oracle(
        q, k, v, rate, seed).sum(), (0, 1, 2))(q, k, v)
    _grad_close(gk, go)


def test_dropout_keeps_kernel_dispatch(monkeypatch):
    """dropout>0 on TPU still dispatches the flash kernel (the reference
    keeps fused SDPA under dropout; round-1 fell back to the jnp path)."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    calls = {}

    def fake_flash(q, k, v, **kwargs):
        calls.update(kwargs)
        return jnp.zeros_like(q)

    monkeypatch.setattr(FA, "flash_attention", fake_flash)
    q, k, v = _qkv(B=1, Hq=2, Hkv=2, T=128, D=64)
    A.causal_attention(q, k, v, dropout_rate=0.1,
                       dropout_rng=jax.random.key(0), platform="tpu")
    assert calls.get("dropout_rate") == 0.1
    assert "seed" in calls


def test_decode_kernel_matches_oracle_interpret():
    """Pallas decode kernel (interpret) vs jnp cached_attention oracle at
    several cache occupancies, incl. GQA and chunked (T>1) decode."""
    from penroz_tpu.ops.pallas import decode_attention as DA
    rng = np.random.default_rng(2)
    B, Hq, Hkv, D, S = 2, 4, 2, 64, 256
    k_full = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v_full = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    for offset, T in [(0, 8), (5, 1), (100, 4), (255, 1), (0, 1)]:
        q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
        off = jnp.asarray(offset, jnp.int32)
        length = jnp.asarray(offset + T, jnp.int32)
        ref = A.cached_attention(q, k_full, v_full, off, length)
        out = DA.decode_attention(q, k_full, v_full, off, length,
                                  block_k=128, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5,
                                   err_msg=f"offset={offset}, T={T}")


def test_decode_kernel_single_kv_head_interpret():
    from penroz_tpu.ops.pallas import decode_attention as DA
    rng = np.random.default_rng(3)
    B, Hq, Hkv, D, S = 1, 1, 1, 128, 128
    k_full = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v_full = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)).astype(np.float32))
    ref = A.cached_attention(q, k_full, v_full, jnp.asarray(17),
                             jnp.asarray(18))
    out = DA.decode_attention(q, k_full, v_full, jnp.asarray(17),
                              jnp.asarray(18), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_decode_kernel_under_jit_interpret():
    """The decode kernel must trace under jit with a traced offset (the
    dispatch condition is static on shapes only)."""
    from penroz_tpu.ops.pallas import decode_attention as DA
    rng = np.random.default_rng(4)
    B, Hq, Hkv, D, S = 1, 2, 1, 64, 128
    k_full = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v_full = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)).astype(np.float32))

    @jax.jit
    def f(q, k, v, off):
        return DA.decode_attention(q, k, v, off, off + 1, interpret=True)

    for off in (0, 63, 127):
        ref = A.cached_attention(q, k_full, v_full, jnp.asarray(off),
                                 jnp.asarray(off + 1))
        np.testing.assert_allclose(np.asarray(f(q, k_full, v_full,
                                                jnp.asarray(off, jnp.int32))),
                                   np.asarray(ref), atol=2e-5)


def test_kernel_gates_respect_platform_hint():
    """A model placed on CPU must never dispatch TPU kernels, regardless of
    the process default backend (regression: device='cpu' /train/ on a
    TPU-attached host crashed with 'Only interpret mode is supported')."""
    q = jnp.zeros((1, 2, 128, 64))
    k = jnp.zeros((1, 2, 128, 64))
    assert not A._use_flash(q, k, platform="cpu")
    assert not A._use_flash_decode(q, k, platform="cpu")
    assert A._use_flash(q, k, platform="tpu")
    assert A._use_flash_decode(q, k, platform="tpu")
    # long caches stay fused: K/V stream through the kernel grid, so there
    # is no VMEM bound on cache capacity (round-1 gate removed) — even a
    # 2M-token cache dispatches the kernel
    k_big = jax.ShapeDtypeStruct((1, 2, 2_097_152, 64), jnp.float32)
    assert A._use_flash_decode(q, k_big, platform="tpu")
    assert not A._use_flash_decode(q, k_big, platform="cpu")


def test_decode_kernel_int8_scales_interpret():
    """Quantized decode path: the kernel's per-tile dequant must match the
    jnp oracle's dense dequantized attention (TurboQuant cache contents)."""
    from penroz_tpu.ops import kv_cache as KV
    from penroz_tpu.ops.pallas import decode_attention as DA
    rng = np.random.default_rng(11)
    B, Hq, Hkv, D, S = 1, 4, 2, 64, 512
    state = KV.QuantKVState.create([(Hkv, D)], B, S, jnp.float32)
    seeded = jnp.asarray(rng.normal(size=(B, Hkv, 300, D)).astype(np.float32))
    qk, qv, _ = state.append_raw(0, seeded, seeded * 0.5 + 1.0)
    ks, vs = state.k_scale[0], state.v_scale[0]
    for offset, T in [(300 - 1, 1), (100, 4), (0, 8)]:
        q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
        off = jnp.asarray(offset, jnp.int32)
        length = jnp.asarray(offset + T, jnp.int32)
        ref = A.cached_attention(q, qk, qv, off, length, platform="cpu",
                                 k_scale=ks, v_scale=vs)
        out = DA.decode_attention(q, qk, qv, off, length, block_k=128,
                                  interpret=True, k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5,
                                   err_msg=f"offset={offset}, T={T}")


def test_quant_append_raw_matches_append_oracle():
    """append_raw + explicit dequant == append's dequantized output."""
    from penroz_tpu.ops import kv_cache as KV
    rng = np.random.default_rng(12)
    k = jnp.asarray(rng.normal(size=(1, 2, 4, 8)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 4, 8)).astype(np.float32))
    a = KV.QuantKVState.create([(2, 8)], 1, 16, jnp.float32)
    b = KV.QuantKVState.create([(2, 8)], 1, 16, jnp.float32)
    fk, fv, n1 = a.append(0, k, v)
    qk, qv, n2 = b.append_raw(0, k, v)
    assert int(n1) == int(n2)
    np.testing.assert_array_equal(
        np.asarray(fk),
        np.asarray(qk.astype(jnp.float32) * b.k_scale[0]))
    np.testing.assert_array_equal(
        np.asarray(fv),
        np.asarray(qv.astype(jnp.float32) * b.v_scale[0]))


def test_decode_kernel_long_cache_interpret():
    """K-tiled decode kernel vs oracle on a cache much longer than one tile,
    at occupancies that end mid-tile, at tile boundaries, and nearly empty
    (the clamped index map must never fetch past the last valid tile)."""
    from penroz_tpu.ops.pallas import decode_attention as DA
    rng = np.random.default_rng(7)
    B, Hq, Hkv, D, S = 1, 4, 2, 64, 2048
    k_full = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v_full = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    for offset, T in [(0, 1), (100, 4), (511, 1), (512, 1), (1000, 8),
                      (2040, 8), (2047, 1)]:
        q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
        off = jnp.asarray(offset, jnp.int32)
        length = jnp.asarray(offset + T, jnp.int32)
        ref = A.cached_attention(q, k_full, v_full, off, length)
        out = DA.decode_attention(q, k_full, v_full, off, length,
                                  block_k=256, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5,
                                   err_msg=f"offset={offset}, T={T}")


def test_paged_kernel_matches_oracle_interpret():
    """Paged Pallas kernel (interpret) vs the dense-gather jnp oracle across
    occupancies, incl. partially filled pages and GQA."""
    from penroz_tpu.ops.pallas import paged_attention as PA
    from penroz_tpu.ops import kv_cache as KV
    rng = np.random.default_rng(5)
    B, Hq, Hkv, D, P, pages = 2, 4, 2, 64, 16, 8
    S_max = P * pages
    state = KV.PagedKVState.create([(Hkv, D)], batch=B, max_len=S_max,
                                   page_size=P)
    # fill 3 pages + 5 tokens
    fill = 3 * P + 5
    k_fill = jnp.asarray(rng.normal(size=(B, Hkv, fill, D)).astype(np.float32))
    v_fill = jnp.asarray(rng.normal(size=(B, Hkv, fill, D)).astype(np.float32))
    state.append_rows(0, k_fill, v_fill)
    state = state.advanced(fill)
    for T in (1, 4):
        q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
        k_new = jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32))
        v_new = jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32))
        trial = KV.PagedKVState(list(state.k), list(state.v), state.counters,
                                state.block_table, state.page_size,
                                state.pages_per_seq)
        flat_k, flat_v, length = trial.append_rows(0, k_new, v_new)
        ref = A.paged_cached_attention(q, flat_k, flat_v, trial.block_table,
                                       P, trial.length, length,
                                       platform="cpu")
        out = PA.paged_decode_attention(q, flat_k, flat_v, trial.block_table,
                                        P, trial.length, length,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, err_msg=f"T={T}")


def test_paged_kernel_gate():
    from penroz_tpu.ops import kv_cache as KV
    q = jnp.zeros((1, 2, 1, 64))
    flat = jnp.zeros((2, 256, 64))  # head-major pool (Hkv, rows, D)
    table = jnp.zeros((1, 4), jnp.int32)
    assert A._use_paged_kernel(q, flat, table, 64, platform="tpu")
    assert not A._use_paged_kernel(q, flat, table, 64, platform="cpu")
    assert not A._use_paged_kernel(q, flat, table, 7, platform="tpu")


def test_paged_kernel_quantized_matches_oracle_interpret():
    """Int8 paged kernel (in-VMEM dequant, interpret mode) vs the jnp
    dequantizing gather oracle."""
    from penroz_tpu.ops.pallas import paged_attention as PA
    from penroz_tpu.ops import kv_cache as KV
    rng = np.random.default_rng(9)
    B, Hq, Hkv, D, P = 2, 4, 2, 64, 16
    state = KV.QuantPagedKVState.create([(Hkv, D)], batch=B, max_len=P * 4,
                                        page_size=P)
    fill = P + 3
    k_fill = jnp.asarray(rng.normal(size=(B, Hkv, fill, D)), jnp.float32)
    v_fill = jnp.asarray(rng.normal(size=(B, Hkv, fill, D)), jnp.float32)
    state.append_rows(0, k_fill, v_fill)
    state = state.advanced(fill)

    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(B, Hkv, 1, D)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, Hkv, 1, D)), jnp.float32)
    flat_k, flat_v, length = state.append_rows(0, k_new, v_new)
    ks, vs = state.k_scale[0], state.v_scale[0]

    ref = A.paged_cached_attention(q, flat_k, flat_v, state.block_table, P,
                                   state.length, length, platform="cpu",
                                   k_scale=ks, v_scale=vs)
    out = PA.paged_decode_attention(q, flat_k, flat_v, state.block_table, P,
                                    state.length, length, k_scale=ks,
                                    v_scale=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_window_matches_oracle_interpret():
    """Sliding-window flash forward (interpret) vs the windowed jnp oracle,
    incl. windows smaller than / equal to a tile and GQA."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    rng = np.random.default_rng(21)
    B, Hq, Hkv, T, D = 1, 4, 2, 512, 64
    q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32))
    for window in (64, 128, 200, 512, 1000):
        ref = A.causal_attention_reference(q, k, v, window=window)
        out = FA.flash_attention(q, k, v, causal=True, block_q=128,
                                 block_k=128, interpret=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, err_msg=f"window={window}")


def test_flash_window_grads_match_oracle_interpret():
    """Windowed dq/dk/dv (interpret) vs the windowed jnp oracle's grads —
    exercises the fully-masked-tile rows in the backward recompute."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    rng = np.random.default_rng(22)
    B, H, T, D = 1, 2, 256, 64
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    window = 96
    ref_g = jax.grad(lambda q, k, v: A.causal_attention_reference(
        q, k, v, window=window).sum(), (0, 1, 2))(q, k, v)
    ker_g = jax.grad(lambda q, k, v: FA.flash_attention(
        q, k, v, True, 128, 128, interpret=True,
        window=window).sum(), (0, 1, 2))(q, k, v)
    for r, o, name in zip(ref_g, ker_g, "qkv"):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=3e-4,
                                   err_msg=f"d{name}")


def test_decode_kernel_window_matches_oracle_interpret():
    """Windowed cached decode (interpret) vs the windowed jnp oracle at
    occupancies where early tiles are fully outside the window."""
    from penroz_tpu.ops.pallas import decode_attention as DA
    rng = np.random.default_rng(23)
    B, Hq, Hkv, D, S = 1, 4, 2, 64, 1024
    k_full = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v_full = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    for window, offset, T in [(64, 700, 1), (128, 511, 4), (256, 100, 8),
                              (32, 1000, 8)]:
        q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
        off = jnp.asarray(offset, jnp.int32)
        length = jnp.asarray(offset + T, jnp.int32)
        ref = A.cached_attention(q, k_full, v_full, off, length,
                                 platform="cpu", window=window)
        out = DA.decode_attention(q, k_full, v_full, off, length,
                                  block_k=128, interpret=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5,
                                   err_msg=f"window={window}, off={offset}")


def test_decode_kernel_window_with_int8_scales_interpret():
    """Sliding window + TurboQuant together: per-tile dequant under the
    band mask matches the dense dequantized windowed oracle."""
    from penroz_tpu.ops import kv_cache as KV
    from penroz_tpu.ops.pallas import decode_attention as DA
    rng = np.random.default_rng(31)
    B, Hq, Hkv, D, S = 1, 4, 2, 64, 512
    state = KV.QuantKVState.create([(Hkv, D)], B, S, jnp.float32)
    seeded = jnp.asarray(rng.normal(size=(B, Hkv, 400, D)).astype(np.float32))
    qk, qv, _ = state.append_raw(0, seeded, seeded * 0.3 - 0.5)
    ks, vs = state.k_scale[0], state.v_scale[0]
    window = 64
    for offset, T in [(399, 1), (200, 4)]:
        q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
        off = jnp.asarray(offset, jnp.int32)
        length = jnp.asarray(offset + T, jnp.int32)
        ref = A.cached_attention(q, qk, qv, off, length, platform="cpu",
                                 k_scale=ks, v_scale=vs, window=window)
        out = DA.decode_attention(q, qk, qv, off, length, block_k=128,
                                  interpret=True, k_scale=ks, v_scale=vs,
                                  window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, err_msg=f"offset={offset}")


def test_window_with_paged_cache_generates(monkeypatch):
    """Paged cache + sliding window: windowed generation through the paged
    pool must equal the contiguous-cache result at T=0."""
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    layers = [
        {"embedding": {"num_embeddings": 32, "embedding_dim": 16}},
        {"residual": [
            {"sequential": [
                {"rmsnorm": {"normalized_shape": 16}},
                {"linear": {"in_features": 16, "out_features": 48}},
                {"attention": {"num_heads": 2, "sliding_window": 4,
                               "dropout": 0.0}},
                {"linear": {"in_features": 16, "out_features": 16}}]}]},
        {"linear": {"in_features": 16, "out_features": 32}},
        {"softmaxlast": {"dim": -1}}]
    model = NeuralNetworkModel("wcombo", Mapper(layers, {"sgd": {"lr": 0.1}}))
    plain = model.generate_tokens([[1, 2]], block_size=16, max_new_tokens=6,
                                  temperature=0.0)
    monkeypatch.setenv("PAGED_KV_CACHE", "1")
    monkeypatch.setenv("PENROZ_KV_PAGE_SIZE", "4")
    paged = model.generate_tokens([[1, 2]], block_size=16, max_new_tokens=6,
                                  temperature=0.0)
    assert paged == plain




def test_paged_kernel_int8_window_matches_oracle_interpret():
    """int8 paged pool + sliding window: the scale pages must ride the SAME
    clamped page lookup as K/V — a divergence would dequantize with wrong
    per-token scales (this is the only combo exercising that branch)."""
    from penroz_tpu.ops import kv_cache as KV
    from penroz_tpu.ops.pallas import paged_attention as PA
    rng = np.random.default_rng(43)
    Hkv, D, page = 2, 64, 8
    state = KV.QuantPagedKVState.create([(Hkv, D)], 1, 128, jnp.float32,
                                        page_size=page)
    fill = jnp.asarray(rng.normal(size=(1, Hkv, 90, D)).astype(np.float32))
    state.append_rows(0, fill, fill * 0.3 - 0.5)
    window = 16
    for offset, T in [(89, 1), (40, 4)]:
        q = jnp.asarray(rng.normal(size=(1, 4, T, D)).astype(np.float32))
        off = jnp.asarray(offset, jnp.int32)
        length = jnp.asarray(offset + T, jnp.int32)
        ref = A.paged_cached_attention(
            q, state.k[0], state.v[0], state.block_table, page, off, length,
            platform="cpu", window=window,
            k_scale=state.k_scale[0], v_scale=state.v_scale[0])
        out = PA.paged_decode_attention(
            q, state.k[0], state.v[0], state.block_table, page, off, length,
            interpret=True, window=window,
            k_scale=state.k_scale[0], v_scale=state.v_scale[0])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, err_msg=f"offset={offset}")


def test_paged_kernel_window_matches_oracle_interpret():
    """Windowed paged kernel (interpret) vs the dense-gather windowed
    oracle, incl. occupancies where whole pages sit below the band."""
    from penroz_tpu.ops import kv_cache as KV
    from penroz_tpu.ops.pallas import paged_attention as PA
    rng = np.random.default_rng(41)
    Hkv, D, page = 2, 64, 8
    state = KV.PagedKVState.create([(Hkv, D)], 1, 128, jnp.float32,
                                   page_size=page)
    fill = jnp.asarray(rng.normal(size=(1, Hkv, 100, D)).astype(np.float32))
    state.append_rows(0, fill, fill * 0.5)
    window = 16
    for offset, T in [(99, 1), (50, 4)]:
        q = jnp.asarray(rng.normal(size=(1, 4, T, D)).astype(np.float32))
        off = jnp.asarray(offset, jnp.int32)
        length = jnp.asarray(offset + T, jnp.int32)
        ref = A.paged_cached_attention(
            q, state.k[0], state.v[0], state.block_table, page, off, length,
            platform="cpu", window=window)
        out = PA.paged_decode_attention(
            q, state.k[0], state.v[0], state.block_table, page, off, length,
            interpret=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, err_msg=f"offset={offset}")


def test_decode_kernel_ragged_lengths_interpret():
    """Per-sequence (B,) lengths: each ragged row matches its own
    single-sequence scalar-length call."""
    from penroz_tpu.ops.pallas import decode_attention as DA
    rng = np.random.default_rng(11)
    B, Hq, Hkv, T, D, S = 3, 4, 2, 1, 64, 256
    lengths = np.array([40, 129, 256], np.int32)
    q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    out = DA.decode_attention(q, k, v, None, jnp.asarray(lengths),
                              interpret=True)
    for b in range(B):
        ref = DA.decode_attention(q[b:b + 1], k[b:b + 1], v[b:b + 1], None,
                                  int(lengths[b]), interpret=True)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref[0]),
                                   atol=2e-5)
    # a scalar length still broadcasts over the batch
    out_s = DA.decode_attention(q, k, v, None, 129, interpret=True)
    ref_s = DA.decode_attention(q, k, v, None,
                                jnp.full((B,), 129, jnp.int32),
                                interpret=True)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(ref_s),
                               atol=1e-6)
    with pytest.raises(ValueError, match="scalar or"):
        DA.decode_attention(q, k, v, None, jnp.ones((2,), jnp.int32),
                            interpret=True)


def test_paged_kernel_ragged_lengths_interpret():
    """Ragged paged decode: each sequence attends only its own page
    occupancy (serving-batch layout)."""
    from penroz_tpu.ops.pallas import paged_attention as PA
    from penroz_tpu.ops import kv_cache as KV
    rng = np.random.default_rng(12)
    B, Hq, Hkv, D, P, pages = 3, 4, 2, 64, 16, 12
    S_max = P * pages // 2  # pool shared; per-seq capacity 6 pages
    state = KV.PagedKVState.create([(Hkv, D)], batch=B, max_len=S_max,
                                   page_size=P)
    fill = 2 * P + 3
    k_fill = jnp.asarray(rng.normal(size=(B, Hkv, fill, D)).astype(np.float32))
    v_fill = jnp.asarray(rng.normal(size=(B, Hkv, fill, D)).astype(np.float32))
    flat_k, flat_v, _ = state.append_rows(0, k_fill, v_fill)
    # ragged: sequence b has (fill - 7b) valid tokens (everyone's pages are
    # allocated to `fill`, shorter rows just stop attending earlier)
    lengths = jnp.asarray([fill, fill - 7, fill - 14], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)).astype(np.float32))
    out = PA.paged_decode_attention(q, flat_k, flat_v, state.block_table, P,
                                    None, lengths, interpret=True)
    for b in range(B):
        ref = PA.paged_decode_attention(
            q[b:b + 1], flat_k, flat_v, state.block_table[b:b + 1], P,
            None, int(lengths[b]), interpret=True)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref[0]),
                                   atol=2e-5)


def test_cached_attention_oracle_ragged_lengths():
    """The jnp fallback honors the same ragged (B,) length contract as the
    kernels: each row matches its own scalar-length call (both windowed
    and full)."""
    rng = np.random.default_rng(13)
    B, Hq, Hkv, T, D, S = 3, 4, 2, 1, 16, 64
    lengths = np.array([9, 33, 64], np.int32)
    q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    for window in (None, 16):
        out = A.cached_attention(q, k, v, None, jnp.asarray(lengths),
                                 platform="cpu", window=window)
        for b in range(B):
            ref = A.cached_attention(
                q[b:b + 1], k[b:b + 1], v[b:b + 1],
                jnp.asarray(int(lengths[b]) - T), int(lengths[b]),
                platform="cpu", window=window)
            np.testing.assert_allclose(np.asarray(out[b]),
                                       np.asarray(ref[0]), atol=1e-5)
    with pytest.raises(ValueError, match="scalar or"):
        A.cached_attention(q, k, v, None, jnp.ones((2,), jnp.int32),
                           platform="cpu")


def test_cached_attention_oracle_ragged_b1():
    """A (1,)-shaped length with B=1 takes the ragged path (offset=None
    accepted) and matches the scalar call — kernel/oracle contract parity."""
    rng = np.random.default_rng(14)
    q = jnp.asarray(rng.normal(size=(1, 4, 1, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 64, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 16)).astype(np.float32))
    out = A.cached_attention(q, k, v, None, jnp.asarray([17], jnp.int32),
                             platform="cpu")
    ref = A.cached_attention(q, k, v, jnp.asarray(16), 17, platform="cpu")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_paged_kernel_fetch_pages_parity_interpret():
    """Multi-page fetch (G pages per grid step) is numerically identical to
    the single-page walk across G values, incl. non-dividing G (ceil
    padding), ragged lengths, and a partially filled last page."""
    from penroz_tpu.ops.pallas import paged_attention as PA
    from penroz_tpu.ops import kv_cache as KV
    rng = np.random.default_rng(11)
    B, Hq, Hkv, D, P, pages = 2, 4, 2, 64, 16, 8
    state = KV.PagedKVState.create([(Hkv, D)], batch=B, max_len=P * pages,
                                   page_size=P)
    fill = 5 * P + 7
    k_fill = jnp.asarray(rng.normal(size=(B, Hkv, fill, D)), jnp.float32)
    v_fill = jnp.asarray(rng.normal(size=(B, Hkv, fill, D)), jnp.float32)
    state.append_rows(0, k_fill, v_fill)
    state = state.advanced(fill)
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(B, Hkv, 1, D)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, Hkv, 1, D)), jnp.float32)
    flat_k, flat_v, length = state.append_rows(0, k_new, v_new)
    # ragged: second sequence pretends to be shorter
    lengths = jnp.asarray([int(length), int(length) - P - 3], jnp.int32)
    for window in (None, 2 * P + 5):
        base = PA.paged_decode_attention(
            q, flat_k, flat_v, state.block_table, P, state.length, lengths,
            interpret=True, window=window, fetch_pages=1)
        for G in (2, 3, 4, 8):
            out = PA.paged_decode_attention(
                q, flat_k, flat_v, state.block_table, P, state.length,
                lengths, interpret=True, window=window, fetch_pages=G)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(base), atol=2e-5,
                err_msg=f"G={G} window={window}")


def test_alibi_slopes_match_hf_bloom():
    """Slopes must equal HF's build_alibi_tensor head biases for power-of
    -two and non-power-of-two head counts."""
    import torch
    from transformers.models.bloom.modeling_bloom import build_alibi_tensor
    from penroz_tpu.ops import attention as attn_ops
    for heads in (4, 8, 6, 12):
        mask = torch.ones(1, 5, dtype=torch.long)
        hf = build_alibi_tensor(mask, heads, torch.float32)  # (H, 1, 5)
        hf_slopes = (hf[:, 0, 1] - hf[:, 0, 0]).numpy()  # per-key step
        np.testing.assert_allclose(attn_ops.alibi_slopes(heads), hf_slopes,
                                   rtol=1e-6, err_msg=str(heads))


def test_alibi_attention_shift_invariance_vs_absolute_form():
    """Our slope*(k-q) bias equals HF's slope*k form after softmax (rows
    differ by a constant), on both the causal and the cached path."""
    from penroz_tpu.ops import attention as attn_ops
    rng = np.random.default_rng(0)
    B, H, T, D = 2, 4, 6, 8
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    slopes = attn_ops.alibi_slopes(H)
    ours = attn_ops.causal_attention_reference(q, k, v, alibi=slopes)

    # absolute-form oracle: bias = slope * k_pos (HF Bloom)
    scale = 1.0 / np.sqrt(D)
    logits = np.einsum("bhtd,bhsd->bhts", np.asarray(q), np.asarray(k)) \
        * scale
    logits = logits + slopes[None, :, None, None] * np.arange(T)[None, None,
                                                                 None, :]
    mask = np.tril(np.ones((T, T), bool))
    logits = np.where(mask, logits, -1e30)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.einsum("bhts,bhsd->bhtd", probs, np.asarray(v))
    np.testing.assert_allclose(np.asarray(ours), want, atol=2e-5)

    # cached path: prefill T tokens then decode 1 == uncached row T-1
    kf = jnp.zeros((B, H, 16, D), jnp.float32).at[:, :, :T].set(k)
    vf = jnp.zeros((B, H, 16, D), jnp.float32).at[:, :, :T].set(v)
    got = attn_ops.cached_attention(q[:, :, -1:], kf, vf,
                                    jnp.asarray(T - 1), jnp.asarray(T),
                                    alibi=slopes)
    np.testing.assert_allclose(np.asarray(got)[:, :, 0], want[:, :, -1],
                               atol=2e-5)


def test_flash_kernel_alibi_matches_oracle_interpret():
    """Flash kernels with ALiBi (interpret): forward AND dq/dk/dv match
    the jnp oracle — the bias is added in-tile from SMEM slopes, and the
    backward recompute must include it or p diverges from the forward."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    B, Hq, Hkv, T, D = 1, 4, 2, 256, 64
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32))
    slopes = A.alibi_slopes(Hq)
    out = FA.flash_attention(q, k, v, True, 128, 128, interpret=True,
                             alibi=slopes)
    ref = A.causal_attention_reference(q, k, v, alibi=slopes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    gf = jax.grad(lambda q, k, v: FA.flash_attention(
        q, k, v, True, 128, 128, interpret=True,
        alibi=slopes).sum(), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: A.causal_attention_reference(
        q, k, v, alibi=slopes).sum(), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        err = float(jnp.abs(a - b).max())
        scale = max(float(jnp.abs(b).max()), 1.0)
        assert err <= 2e-4 * scale, f"d{name}: {err}"


@pytest.mark.parametrize("ragged", [False, True])
def test_decode_kernel_alibi_matches_oracle(ragged):
    """Decode kernel with ALiBi (interpret) == the jnp cached oracle —
    per-query-row slopes as a VMEM operand, scalar and ragged lengths."""
    from penroz_tpu.ops.pallas import decode_attention as DA
    B, Hq, Hkv, T, D, S = 2, 4, 2, 1, 64, 256
    rng = np.random.default_rng(21)
    q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    slopes = A.alibi_slopes(Hq)
    if ragged:
        length = jnp.asarray([97, 41], jnp.int32)
        offset = None
    else:
        length = jnp.asarray(97)
        offset = jnp.asarray(96)
    got = DA.decode_attention(q, k, v, offset, length, block_k=128,
                              interpret=True, alibi=slopes)
    want = A.cached_attention(q, k, v, offset, length, platform="cpu",
                              alibi=slopes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_kernel_alibi_matches_oracle(quantized):
    """Paged decode kernel with ALiBi (interpret) == the dense-gather jnp
    oracle, fp and int8 pools, ragged lengths."""
    from penroz_tpu.ops.pallas import paged_attention as PA
    from penroz_tpu.ops import kv_cache as KV
    B, Hq, Hkv, T, D = 2, 4, 2, 1, 64
    page, pages_per_seq, num_pages = 128, 4, 12
    rng = np.random.default_rng(23)
    q = jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32))
    rows = num_pages * page
    slopes = A.alibi_slopes(Hq)
    if quantized:
        kq = jnp.asarray(rng.integers(-127, 127, (Hkv, rows, D)), jnp.int8)
        vq = jnp.asarray(rng.integers(-127, 127, (Hkv, rows, D)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.02, (Hkv, rows, 1)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.02, (Hkv, rows, 1)),
                         jnp.float32)
        scales = {"k_scale": ks, "v_scale": vs}
        flat_k, flat_v = kq, vq
    else:
        flat_k = jnp.asarray(rng.normal(size=(Hkv, rows, D)), jnp.float32)
        flat_v = jnp.asarray(rng.normal(size=(Hkv, rows, D)), jnp.float32)
        scales = {}
    table = jnp.asarray(rng.permutation(num_pages)[:B * pages_per_seq]
                        .reshape(B, pages_per_seq), jnp.int32)
    lengths = jnp.asarray([300, 170], jnp.int32)
    got = PA.paged_decode_attention(q, flat_k, flat_v, table, page, None,
                                    lengths, interpret=True, alibi=slopes,
                                    **scales)
    want = A.paged_cached_attention(q, flat_k, flat_v, table, page, None,
                                    lengths, platform="cpu", alibi=slopes,
                                    **scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5)


def test_decode_kernel_softcap_and_scale_matches_oracle():
    """Decode kernel with Gemma-2 soft-capping + scale override
    (interpret) == the jnp cached oracle."""
    from penroz_tpu.ops.pallas import decode_attention as DA
    B, H, T, D, S = 2, 2, 1, 64, 256
    rng = np.random.default_rng(41)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32)) * 4
    k = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    got = DA.decode_attention(q, k, v, jnp.asarray(96), jnp.asarray(97),
                              block_k=128, interpret=True, softcap=2.0,
                              scale=0.05)
    want = A.cached_attention(q, k, v, jnp.asarray(96), jnp.asarray(97),
                              platform="cpu", softcap=2.0, scale=0.05)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_paged_kernel_softcap_matches_oracle():
    from penroz_tpu.ops.pallas import paged_attention as PA
    B, H, T, D = 1, 2, 1, 64
    page, pages_per_seq, num_pages = 128, 3, 6
    rng = np.random.default_rng(42)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32)) * 4
    rows = num_pages * page
    flat_k = jnp.asarray(rng.normal(size=(H, rows, D)), jnp.float32)
    flat_v = jnp.asarray(rng.normal(size=(H, rows, D)), jnp.float32)
    table = jnp.asarray(rng.permutation(num_pages)[:pages_per_seq][None],
                        jnp.int32)
    got = PA.paged_decode_attention(q, flat_k, flat_v, table, page,
                                    jnp.asarray(200), jnp.asarray(201),
                                    interpret=True, softcap=3.0, scale=0.07)
    want = A.paged_cached_attention(q, flat_k, flat_v, table, page,
                                    jnp.asarray(200), jnp.asarray(201),
                                    platform="cpu", softcap=3.0, scale=0.07)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_kernel_scale_override_value_and_grads():
    """Flash kernels honor the attention-scale override (Gemma-style
    query_pre_attn_scalar) in the forward AND the dq/dkv recompute."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    B, H, T, D = 1, 2, 256, 64
    rng = np.random.default_rng(44)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype(np.float32))
    out = FA.flash_attention(q, k, v, True, 128, 128, interpret=True,
                             scale=0.05)
    ref = A.causal_attention_reference(q, k, v, scale=0.05)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    gf = jax.grad(lambda q, k, v: FA.flash_attention(
        q, k, v, True, 128, 128, interpret=True,
        scale=0.05).sum(), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: A.causal_attention_reference(
        q, k, v, scale=0.05).sum(), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        err = float(jnp.abs(a - b).max())
        scale = max(float(jnp.abs(b).max()), 1.0)
        assert err <= 2e-4 * scale, f"d{name}: {err}"


def test_softcap_reference_fallback_warns_once(monkeypatch):
    """causal_attention with a logit softcap (Gemma-2 training/prefill)
    reroutes to the O(T^2) jnp reference — satellite: that fallback must
    emit the one-time trace-time warning the other fallbacks already emit.
    Asserted via a logger-method spy, not caplog — other suite tests
    reconfigure logging handlers, which silently empties caplog (same
    hazard the parallel-suite tests document)."""
    import logging
    monkeypatch.setattr(A, "_WARNED_ONCE", set())
    warnings = []
    logger = logging.getLogger("penroz_tpu.ops.attention")
    monkeypatch.setattr(logger, "warning",
                        lambda msg, *a: warnings.append(msg % a))
    q, k, v = _qkv()
    got = A.causal_attention(q, k, v, softcap=2.0)
    want = A.causal_attention_reference(q, k, v, softcap=2.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    assert len(warnings) == 1 and "softcap" in warnings[0], warnings
    A.causal_attention(q, k, v, softcap=2.0)  # one-time: no repeat spam
    assert len(warnings) == 1


# -- flash plans: every way the kernels may tile and walk the score matrix ----


def _plan_inputs(seed, B=1, Hq=4, Hkv=2, T=512, D=64):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(B, Hq, T, D)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(B, Hkv, T, D)).astype(np.float32)))


def _noncausal_oracle(q, k, v):
    B, Hq, T, D = q.shape
    group = Hq // k.shape[1]
    out = A._attend(A._group_query_heads(q, group), k, v,
                    jnp.ones((T, k.shape[2]), bool))
    return out.reshape(B, Hq, T, D)


# feature → (flash_attention kwargs, oracle); GQA (4 query heads on 2) in all
_FLASH_FEATURES = {
    "causal": ({}, lambda q, k, v: A.causal_attention_reference(q, k, v)),
    "noncausal": ({"causal": False}, _noncausal_oracle),
    "window": ({"window": 200}, lambda q, k, v:
               A.causal_attention_reference(q, k, v, window=200)),
    "window_in_tile": ({"window": 64}, lambda q, k, v:
                       A.causal_attention_reference(q, k, v, window=64)),
    "alibi": ({"alibi": A.alibi_slopes(4)}, lambda q, k, v:
              A.causal_attention_reference(q, k, v,
                                           alibi=A.alibi_slopes(4))),
    "dropout": ({"dropout_rate": 0.3, "seed": 1234}, lambda q, k, v:
                _masked_dropout_oracle(q, k, v, 0.3, 1234)),
    "scale": ({"scale": 0.05}, lambda q, k, v:
              A.causal_attention_reference(q, k, v, scale=0.05)),
}
# plan → (block_q, block_k, vmem_budget or None for the default)
_FLASH_PLANS = {
    "resident128": (128, 128, None),
    "resident256": (256, 256, None),
    "resident512": (512, 512, None),
    "resident128x256": (128, 256, None),
    "resident256x128": (256, 128, None),
    "chunked128": (128, 128, 1),
    "chunked256x128": (256, 128, 1),
    # square tiles a window's budget streams: the tile on the diagonal is
    # cut in the chunked forward too
    "chunked256": (256, 256, 1),
}
# (plan, the grain a tile on the diagonal is cut at): the plan's own for
# every plan, and each other grain of the square tiles it can cut (a grain
# of the tile's size: the tile is done whole)
_FLASH_GRAINS = ([(plan, 128) for plan in _FLASH_PLANS]
                 + [("resident512", 256), ("resident512", 512),
                    ("resident256", 256), ("chunked256", 256)])


def _use_grain(monkeypatch, grain):
    """The grains are the plan's to choose (no argument sets them): a test
    moves the constants the plan reads, the forward's and the one-pass
    backward's alike."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    monkeypatch.setattr(FA, "_DIAG_GRAIN", grain)
    monkeypatch.setattr(FA, "_BWD_DIAG_GRAIN", grain)


@pytest.mark.parametrize("plan_name,grain", _FLASH_GRAINS)
@pytest.mark.parametrize("feature", list(_FLASH_FEATURES))
def test_flash_plans_match_oracle(feature, plan_name, grain, monkeypatch):
    """Forward and dq/dk/dv (interpret) against the jnp oracle under every
    plan the function can return: K/V resident with in-kernel walks and the
    one-pass backward (several heads a grid step), and the chunked kernels
    with the two-kernel backward a small budget forces; the tile on the
    diagonal whole and cut at each grain."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    _use_grain(monkeypatch, grain)
    kwargs, oracle = _FLASH_FEATURES[feature]
    block_q, block_k, budget = _FLASH_PLANS[plan_name]
    q, k, v = _plan_inputs(len(feature) + len(plan_name))
    plan = FA.plan_flash(512, 512, 64, 4, kwargs.get("causal", True),
                         kwargs.get("window"), heads=4, group=2,
                         block_q=block_q, block_k=block_k,
                         **({} if budget is None
                            else {"vmem_budget": budget}))
    causal = kwargs.get("causal", True)
    cut = block_q == block_k and grain < block_q and causal
    assert plan.diag_grain == (grain if cut else block_q)
    # the two-kernel backward does its tiles whole
    assert plan.bwd_diag_grain == (grain if cut and budget is None
                                   else block_q)
    whole = FA.computed_over_live(512, 512, block_q, block_k, block_q,
                                  causal, kwargs.get("window"))
    assert (plan.computed_over_live < whole) == cut
    assert (plan.bwd_computed_over_live < whole) == (cut and budget is None)
    if budget is None:
        assert plan.resident and plan.fused_bwd
        # several heads a grid step, but where 512-tiles leave no room
        assert plan.heads_per_step > 1 or block_q == 512
    else:
        assert not plan.resident and not plan.fused_bwd
    assert (plan.block_q, plan.block_k) == (block_q, block_k)

    def attend(q, k, v):
        return FA.flash_attention(
            q, k, v, block_q=block_q, block_k=block_k, interpret=True,
            **kwargs, **({} if budget is None else {"vmem_budget": budget}))

    np.testing.assert_allclose(np.asarray(attend(q, k, v)),
                               np.asarray(oracle(q, k, v)), atol=2e-5)
    # a non-uniform cotangent: .sum() alone would leave δ = Σ dO·O blind
    w = jnp.asarray(np.random.default_rng(9).normal(size=q.shape)
                    .astype(np.float32))
    gf = jax.grad(lambda q, k, v: (attend(q, k, v) * w).sum(),
                  (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: (oracle(q, k, v) * w).sum(),
                  (0, 1, 2))(q, k, v)
    _grad_close(gf, gr)


@pytest.mark.parametrize("case,want", [
    # GPT-2 124M, the benchmark cell's attention: (12, 12, 1024, 64) bf16
    (dict(T=1024, S=1024, D=64, itemsize=2, heads=12),
     dict(resident=True, q_rows=1024, fused_bwd=True, block_q=512,
          diag_grain=256, bwd_diag_grain=128, computed_over_live=1.25,
          bwd_computed_over_live=1.125)),
    # … not causal: no diagonal, nothing dead to compute
    (dict(T=1024, S=1024, D=64, itemsize=2, heads=12, causal=False),
     dict(block_q=512, diag_grain=512, bwd_diag_grain=512,
          computed_over_live=1.0, bwd_computed_over_live=1.0)),
    # … under a window inside one sub-block: of the two tiles on the
    # diagonal the sub-blocks the band meets (3 of 256, 7 of 128), and the
    # whole tile left of the second
    (dict(T=1024, S=1024, D=64, itemsize=2, heads=12, window=64),
     dict(diag_grain=256, bwd_diag_grain=128,
          computed_over_live=(2 * 3 * 256 * 256 + 512 * 512) / (1024 * 64),
          bwd_computed_over_live=(2 * 7 * 128 * 128 + 512 * 512)
          / (1024 * 64))),
    # GPT-2-large: D = 64 × 20 heads
    (dict(T=1024, S=1024, D=64, itemsize=2, heads=20),
     dict(resident=True, q_rows=1024, fused_bwd=True)),
    # long context, GQA: K/V of a head still fit the default share, the
    # seven (T, D) operands of the one-pass backward only what its call asks
    # the compiler for (the looped cell's shape; 36 tiles, 8 of them cut)
    (dict(T=4096, S=4096, D=128, itemsize=2, heads=32, group=4),
     dict(resident=True, q_rows=1024, fused_bwd=True, heads_per_step=1,
          bwd_diag_grain=128, bwd_computed_over_live=1.03125,
          bwd_vmem_bytes=int(24.125 * 2 ** 20))),
    # … the sides of the lengths around it at D = 128: one pass with a
    # limit from T = 2048 to 8192, the two kernels from 16384
    (dict(T=2048, S=2048, D=128, itemsize=2, heads=32, group=4),
     dict(resident=True, q_rows=2048, fused_bwd=True, heads_per_step=1,
          bwd_diag_grain=128, bwd_computed_over_live=1.0625,
          bwd_vmem_bytes=int(15.3125 * 2 ** 20))),
    (dict(T=8192, S=8192, D=128, itemsize=2, heads=32, group=4),
     dict(resident=False, fused_bwd=True, heads_per_step=1,
          bwd_diag_grain=128, bwd_computed_over_live=1.015625,
          bwd_vmem_bytes=int(41.75 * 2 ** 20))),
    (dict(T=16384, S=16384, D=128, itemsize=2, heads=32, group=4),
     dict(resident=False, fused_bwd=False, heads_per_step=1,
          bwd_diag_grain=512, bwd_computed_over_live=1.03125,
          bwd_vmem_bytes=0)),
    # … and in the model's layout, where D = 256 is a block as wide
    (dict(T=4096, S=4096, D=256, itemsize=2, heads=8, group=4,
          layout="btd"),
     dict(fused_bwd=True, bwd_diag_grain=128, heads_per_block=1)),
    # one tile
    (dict(T=128, S=128, D=64, itemsize=2, heads=4),
     dict(block_q=128, block_k=128, bwd_block_q=128, bwd_block_k=128,
          resident=True, q_rows=128, fused_bwd=True, heads_per_step=4,
          diag_grain=128, bwd_diag_grain=128, computed_over_live=2.0)),
    # K/V of a head past the budget: stream them
    (dict(T=32768, S=32768, D=128, itemsize=2, heads=8),
     dict(resident=False, fused_bwd=False, heads_per_step=1, diag_grain=256,
          bwd_diag_grain=512,
          computed_over_live=(64 * 63 / 2 + 64 * 0.75) / (64 * 64 / 2),
          bwd_computed_over_live=(64 * 63 / 2 + 64) / (64 * 64 / 2))),
    # a budget narrowed below the default narrows what the backward may ask
    # for with it
    (dict(T=1024, S=1024, D=64, itemsize=2, heads=12, vmem_budget=2 ** 20),
     dict(resident=False, fused_bwd=False, heads_per_step=1,
          bwd_vmem_bytes=0)),
    # T = 4096, D = 64: resident forward in bf16 (on part of the queries a
    # step), streamed in f32; the backward in one pass in both
    (dict(T=4096, S=4096, D=64, itemsize=2, heads=1),
     dict(resident=True, fused_bwd=True, heads_per_step=1,
          bwd_diag_grain=128, bwd_computed_over_live=1.03125,
          bwd_vmem_bytes=int(24.125 * 2 ** 20))),
    (dict(T=4096, S=4096, D=64, itemsize=4, heads=1),
     dict(resident=False, fused_bwd=True, heads_per_step=1)),
])
def test_flash_plan_function(case, want):
    from penroz_tpu.ops.pallas import flash_attention as FA
    plan = FA.plan_flash(**case)
    got = {key: getattr(plan, key) for key in want}
    assert got == want, plan.describe()
    heads, group = case["heads"], case.get("group", 1)
    hps = plan.heads_per_step
    # a step owns whole K/V heads or a share of one
    assert heads % hps == 0 and (hps % group == 0 or group % hps == 0)
    for block, n in ((plan.block_q, case["T"]), (plan.block_k, case["S"]),
                     (plan.bwd_block_q, case["T"]),
                     (plan.bwd_block_k, case["S"])):
        assert n % block == 0 and block % 128 == 0
    assert case["T"] % plan.q_rows == 0 and plan.q_rows % plan.block_q == 0
    if not plan.resident:
        assert plan.q_rows == plan.block_q
    # the estimates the plan was chosen by stay inside the budget, the
    # one-pass backward's inside what its call may ask for — and the plan
    # carries that one (a lane block is one head as wide)
    budget = case.get("vmem_budget", FA.VMEM_BUDGET)
    kvh = FA._kv_heads_per_step(hps, group)
    if plan.resident:
        assert FA._fwd_resident_bytes(
            plan.q_rows, case["S"], case["D"], case["itemsize"], hps, kvh,
            plan.block_q, plan.block_k) <= budget
    if plan.fused_bwd and case.get("layout") != "btd":
        assert plan.bwd_vmem_bytes == FA._bwd_fused_bytes(
            case["T"], case["S"], case["D"], case["itemsize"], hps, kvh,
            plan.bwd_block_q, plan.bwd_block_k)
    # … with Mosaic's share on top of it
    assert FA._asked(FA.VMEM_BUDGET) == 16 * 2 ** 20    # the default
    assert (FA._asked(plan.bwd_vmem_bytes) <= FA.VMEM_LIMIT
            if budget >= FA.VMEM_BUDGET else plan.bwd_vmem_bytes <= budget)
    assert (plan.bwd_vmem_bytes > 0) == plan.fused_bwd
    # several heads a step only where the backward asks for nothing
    assert hps == plan.heads_per_block or plan.bwd_vmem_bytes <= budget
    # pure: the same shapes give the same plan, and it names itself
    assert FA.plan_flash(**case) == plan
    assert (f"{'fused_bwd' if plan.fused_bwd else 'split_bwd'} "
            f"bwd_vmem_mib={plan.bwd_vmem_bytes / 2 ** 20:.1f} "
            f"heads_per_step={hps} ") in plan.describe()
    assert (f"diag_grain={plan.diag_grain} "
            f"bwd_diag_grain={plan.bwd_diag_grain} "
            f"computed_over_live={plan.computed_over_live:.3f} "
            f"bwd_computed_over_live={plan.bwd_computed_over_live:.3f} "
            in plan.describe())
    # a grain cuts the tiles it is chosen for into whole sub-blocks
    for grain, block in ((plan.diag_grain, plan.block_q),
                         (plan.bwd_diag_grain, plan.bwd_block_q)):
        assert block % grain == 0 and grain % 128 == 0
    if not plan.fused_bwd:
        assert plan.bwd_diag_grain == plan.bwd_block_q


def test_flash_plan_honours_explicit_tiles_and_rejects_ragged_lengths():
    from penroz_tpu.ops.pallas import flash_attention as FA
    plan = FA.plan_flash(1024, 1024, 64, 2, heads=12, block_q=128,
                         block_k=512)
    assert (plan.block_q, plan.block_k, plan.bwd_block_q,
            plan.bwd_block_k) == (128, 512, 128, 512)
    # a tile that does not divide T falls to one that does (384 = 3 × 128)
    assert FA.plan_flash(384, 384, 64, 4, block_q=256,
                         block_k=256).block_q == 128
    with pytest.raises(ValueError, match="requires T%"):
        FA.plan_flash(200, 200 + 128, 64, 4, block_q=128, block_k=128)


@pytest.mark.parametrize("window", [None, 1, 64, 128, 200, 500, 4000])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 256),
                                             (512, 512), (128, 512),
                                             (512, 128), (256, 128)])
def test_flash_walks_exactly_the_live_tiles(block_q, block_k, window):
    """The key tiles a query tile walks (forward, dq) and the query tiles a
    key tile walks (one-pass backward, dkv) are exactly the tiles the band
    meets; the unmasked walk takes exactly those wholly inside it; and a
    dead step of the chunked grid is clamped onto a live tile."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    T = S = 1024
    q_pos, k_pos = np.arange(T)[:, None], np.arange(S)[None, :]
    band = k_pos <= q_pos
    if window is not None:
        band &= k_pos > q_pos - window
    num_q, num_k = T // block_q, S // block_k
    tiles = band.reshape(num_q, block_q, num_k, block_k)
    live = tiles.any(axis=(1, 3))
    full = tiles.all(axis=(1, 3))

    walked = np.zeros_like(live)
    unmasked = np.zeros_like(live)
    clamp = FA._clamped(FA.key_tile_ranges, block_q, block_k, num_k, True,
                        window)
    for qi in range(num_q):
        lo, full_lo, full_hi, hi = FA.key_tile_ranges(
            qi, block_q, block_k, num_k, True, window)
        assert 0 <= lo <= full_lo <= full_hi <= hi <= num_k
        walked[qi, lo:hi] = True
        unmasked[qi, full_lo:full_hi] = True
        for kj in range(num_k):
            assert live[qi, clamp(qi, kj)]
            assert clamp(qi, kj) == kj or not live[qi, kj]
    np.testing.assert_array_equal(walked, live)
    np.testing.assert_array_equal(unmasked, full)

    walked[:] = unmasked[:] = False
    clamp = FA._clamped(FA.query_tile_ranges, block_q, block_k, num_q, True,
                        window)
    for kj in range(num_k):
        lo, full_lo, full_hi, hi = FA.query_tile_ranges(
            kj, block_q, block_k, num_q, True, window)
        assert 0 <= lo <= full_lo <= full_hi <= hi <= num_q
        walked[lo:hi, kj] = True
        unmasked[full_lo:full_hi, kj] = True
        if live[:, kj].any():
            for qi in range(num_q):
                assert live[clamp(kj, qi), kj]
    np.testing.assert_array_equal(walked, live)
    np.testing.assert_array_equal(unmasked, full)
    # non-causal: every tile, none masked
    assert FA.key_tile_ranges(1, block_q, block_k, num_k, False, None) == \
        (0, 0, num_k, num_k)


def test_flash_plan_is_logged_once_and_spanned_per_trace(caplog):
    """The counter that says which plan engaged: one INFO line per distinct
    (shape, plan), and a ``penroz/flash_plan`` span with the plan's fields
    under whatever span of a job's trace is compiling."""
    import logging
    from penroz_tpu.ops.pallas import flash_attention as FA
    from penroz_tpu.utils import tracing
    q, k, v = _plan_inputs(0, Hq=2, Hkv=2, T=384)
    FA._log_plan.cache_clear()
    tracing.reset()
    trace = tracing.maybe_trace("flash-plan-job", job=True, route="/train/")
    with caplog.at_level(logging.INFO, logger=FA.__name__), \
            tracing.use(trace), tracing.span("penroz/train_dispatch"):
        for _ in range(2):
            jax.jit(lambda q, k, v: FA.flash_attention(
                q, k, v, interpret=True)).lower(q, k, v)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("flash plan:")]
    assert len(lines) == 1, lines
    assert lines[0].startswith("flash plan: T=384 S=384 D=64 bq=384 bk=384")
    assert " resident q_rows=384 fused_bwd bwd_vmem_mib=" in lines[0]
    # one 384-tile, which 256 does not divide: cut at 128 both ways, 6 of
    # its 9 sub-blocks, over half the tile
    assert (" diag_grain=128 bwd_diag_grain=128 computed_over_live=1.333 "
            "bwd_computed_over_live=1.333 ") in lines[0]
    dispatch = trace.to_dict()["root"]["children"][0]
    spans = [c for c in dispatch["children"]
             if c["name"] == "penroz/flash_plan"]
    assert len(spans) == 2
    meta = spans[0]["meta"]
    assert (meta["T"], meta["S"], meta["D"]) == (384, 384, 64)
    assert meta["block_q"] == 384 and meta["resident"] is True
    assert meta["fused_bwd"] is True and meta["heads_per_step"] == 2
    # what the one-pass backward counts on, under the default share: its
    # call asks the compiler for nothing
    assert 0 < meta["bwd_vmem_bytes"] <= FA.VMEM_BUDGET
    assert f"bwd_vmem_mib={meta['bwd_vmem_bytes'] / 2 ** 20:.1f} " in lines[0]
    assert meta["diag_grain"] == meta["bwd_diag_grain"] == 128
    assert meta["computed_over_live"] == pytest.approx(4 / 3)
    assert meta["bwd_computed_over_live"] == pytest.approx(4 / 3)
    trace.finish("completed")


# -- the (B, T, lanes) layout: flash_attention_btd and the module's path ------


def _heads_first(x, heads):
    B, T, width = x.shape
    return x.reshape(B, T, heads, width // heads).transpose(0, 2, 1, 3)


def _btd_parts(qkv, Hq, Hkv, D):
    return (qkv[..., :Hq * D], qkv[..., Hq * D:(Hq + Hkv) * D],
            qkv[..., (Hq + Hkv) * D:])


def _btd_oracle(oracle, Hq, Hkv, D):
    """A (B, H, T, D) oracle over the ranges of a fused (B, T, ·) array."""
    def run(qkv):
        q, k, v = _btd_parts(qkv, Hq, Hkv, D)
        out = oracle(_heads_first(q, Hq), _heads_first(k, Hkv),
                     _heads_first(v, Hkv))
        return out.transpose(0, 2, 1, 3).reshape(*qkv.shape[:2], Hq * D)
    return run


# shape → (B, Hq, Hkv, T, D); the D = 64 cases pair heads in a lane block
_BTD_SHAPES = {
    "d64_pairs": (2, 4, 4, 256, 64),
    "d128": (1, 2, 2, 256, 128),
    "d128_gqa": (1, 4, 2, 256, 128),
    "d256_gqa": (1, 2, 1, 128, 256),
}
_BTD_FEATURES = {
    "causal": lambda Hq: ({}, A.causal_attention_reference),
    "window": lambda Hq: ({"window": 100}, lambda q, k, v:
                          A.causal_attention_reference(q, k, v, window=100)),
    "alibi": lambda Hq: ({"alibi": A.alibi_slopes(Hq)}, lambda q, k, v:
                         A.causal_attention_reference(
                             q, k, v, alibi=A.alibi_slopes(Hq))),
    # the same keep-mask as the (B, H, T, D) kernels draw
    "dropout": lambda Hq: ({"dropout_rate": 0.3, "seed": 1234},
                           lambda q, k, v: _masked_dropout_oracle(
                               q, k, v, 0.3, 1234)),
}


@pytest.mark.parametrize("form", ["fused", "split"])
@pytest.mark.parametrize("feature", list(_BTD_FEATURES))
@pytest.mark.parametrize("shape", list(_BTD_SHAPES))
def test_flash_btd_matches_oracle(shape, feature, form):
    """``flash_attention_btd`` (interpret) against the jnp oracle, output
    and the gradient of every range of the projection: q, k, v as lane
    ranges of the one fused array, and as three arrays."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    B, Hq, Hkv, T, D = _BTD_SHAPES[shape]
    kwargs, oracle = _BTD_FEATURES[feature](Hq)
    rng = np.random.default_rng(len(shape) + len(feature))
    qkv = jnp.asarray(rng.normal(size=(B, T, (Hq + 2 * Hkv) * D))
                      .astype(np.float32))
    w = jnp.asarray(rng.normal(size=(B, T, Hq * D)).astype(np.float32))

    def attend(qkv):
        arrays = (qkv,) if form == "fused" else _btd_parts(qkv, Hq, Hkv, D)
        return FA.flash_attention_btd(*arrays, heads=Hq, kv_heads=Hkv,
                                      block_q=128, block_k=128,
                                      interpret=True, **kwargs)

    want = _btd_oracle(oracle, Hq, Hkv, D)
    np.testing.assert_allclose(np.asarray(attend(qkv)),
                               np.asarray(want(qkv)), atol=2e-5)
    got_g = jax.grad(lambda x: (attend(x) * w).sum())(qkv)
    want_g = jax.grad(lambda x: (want(x) * w).sum())(qkv)
    _grad_close(_btd_parts(got_g, Hq, Hkv, D),
                _btd_parts(want_g, Hq, Hkv, D))


# tile → the grains its diagonal tiles are cut at (the tile's own: whole)
_BTD_GRAINS = [(512, 128), (512, 256), (512, 512), (256, 128)]
_BTD_GRAIN_FEATURES = dict(
    _BTD_FEATURES, window_in_tile=lambda Hq: (
        {"window": 64},
        lambda q, k, v: A.causal_attention_reference(q, k, v, window=64)))


@pytest.mark.parametrize("tile,grain", _BTD_GRAINS)
@pytest.mark.parametrize("feature", list(_BTD_GRAIN_FEATURES))
@pytest.mark.parametrize("shape", ["d64_pairs", "d128_gqa"])
def test_flash_btd_diagonal_grain_matches_oracle(shape, feature, tile, grain,
                                                 monkeypatch):
    """The model's layout with tiles large enough to cut: the tile on the
    diagonal at each grain and whole, head pairs and one head a lane block,
    output and the gradient of the fused projection against the oracle."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    _use_grain(monkeypatch, grain)
    _, Hq, Hkv, _, D = _BTD_SHAPES[shape]
    T = 512
    kwargs, oracle = _BTD_GRAIN_FEATURES[feature](Hq)
    plan = FA.plan_flash(T, T, D, 4, True, kwargs.get("window"), heads=Hq,
                         group=Hq // Hkv, block_q=tile, block_k=tile,
                         layout="btd")
    assert plan.resident and plan.fused_bwd
    assert plan.diag_grain == plan.bwd_diag_grain == grain
    rng = np.random.default_rng(len(shape) + len(feature) + tile + grain)
    qkv = jnp.asarray(rng.normal(size=(1, T, (Hq + 2 * Hkv) * D))
                      .astype(np.float32))
    w = jnp.asarray(rng.normal(size=(1, T, Hq * D)).astype(np.float32))
    attend = lambda x: FA.flash_attention_btd(
        x, heads=Hq, kv_heads=Hkv, block_q=tile, block_k=tile,
        interpret=True, **kwargs)
    want = _btd_oracle(oracle, Hq, Hkv, D)
    np.testing.assert_allclose(np.asarray(attend(qkv)),
                               np.asarray(want(qkv)), atol=2e-5)
    got_g = jax.grad(lambda x: (attend(x) * w).sum())(qkv)
    want_g = jax.grad(lambda x: (want(x) * w).sum())(qkv)
    _grad_close(_btd_parts(got_g, Hq, Hkv, D),
                _btd_parts(want_g, Hq, Hkv, D))


def _cell_like_qkv(dtype, heads=2, T=1024, D=64):
    rng = np.random.default_rng(38)
    return (jnp.asarray(rng.normal(size=(1, T, 3 * heads * D)), dtype),
            jnp.asarray(rng.normal(size=(1, T, heads * D)), jnp.float32))


def test_flash_btd_cell_plan_matches_oracle():
    """The benchmark cell's own plan — T = 1024, D = 64, bfloat16, the fused
    projection, 512-tiles of which two of a head's three lie on the
    diagonal and are cut at the plan's grain — on one head pair, against the
    oracle in float32 on the same inputs."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    qkv, w = _cell_like_qkv(jnp.bfloat16)
    cell = FA.plan_flash(1024, 1024, 64, 2, heads=12, layout="btd",
                         fused_qkv=True)
    plan = FA.plan_flash(1024, 1024, 64, 2, heads=2, layout="btd",
                         fused_qkv=True)
    assert plan == cell and plan.describe() == (
        "bq=512 bk=512 bwd_bq=512 bwd_bk=512 diag_grain=256 "
        "bwd_diag_grain=128 computed_over_live=1.250 "
        "bwd_computed_over_live=1.125 resident q_rows=1024 fused_bwd "
        "bwd_vmem_mib=10.9 heads_per_step=2 layout=btd heads_per_block=2 "
        "fused_qkv")
    assert plan.bwd_vmem_bytes <= FA.VMEM_BUDGET      # no limit asked for
    attend = lambda x: FA.flash_attention_btd(x, heads=2, interpret=True)
    want = _btd_oracle(A.causal_attention_reference, 2, 2, 64)
    exact = qkv.astype(jnp.float32)
    pairs = [(attend(qkv), want(exact)),
             (jax.grad(lambda x: (attend(x).astype(jnp.float32) * w).sum())(
                 qkv),
              jax.grad(lambda x: (want(x) * w).sum())(exact))]
    for got, ref in pairs:
        got = np.asarray(got.astype(jnp.float32))
        # bfloat16's rounding of p, dS and the results: 4e-3 of the norm
        assert np.linalg.norm(got - ref) <= 1e-2 * np.linalg.norm(ref)


def _ulps_bf16(a, b):
    """|a − b| in units of the last place of bfloat16 (8 significant bits)
    at the size of the larger of the two — or of the array's mean entry,
    for an entry whose terms cancelled to less: its last places are the
    float32 accumulations', whatever their order."""
    a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
    top = np.maximum(np.maximum(np.abs(a), np.abs(b)), np.abs(b).mean())
    return np.abs(a - b) / np.exp2(np.floor(np.log2(top)) - 7)


@pytest.mark.parametrize("grain", [128, 256])
def test_flash_cut_diagonal_tiles_equal_whole_ones(grain, monkeypatch):
    """The same mathematics: a dead entry gave exp(−1e30 − m) = 0 to every
    sum and a zero product to every accumulation, so on the same bfloat16
    inputs the kernels that leave the dead sub-blocks out return what the
    whole-tile kernels return, output and gradient, to one unit in
    bfloat16's last place (the order of the float32 accumulations is all
    that differs, and where it moves a bfloat16 rounding of p or dS)."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    qkv, w = _cell_like_qkv(jnp.bfloat16)

    def run(g):
        _use_grain(monkeypatch, g)
        plan = FA.plan_flash(1024, 1024, 64, 2, heads=2, layout="btd",
                             fused_qkv=True)
        assert plan.diag_grain == plan.bwd_diag_grain == g
        attend = lambda x: FA.flash_attention_btd(x, heads=2, interpret=True)
        return attend(qkv), jax.grad(
            lambda x: (attend(x).astype(jnp.float32) * w).sum())(qkv)

    for got, want in zip(run(grain), run(512)):
        assert _ulps_bf16(got, want).max() <= 1.0


def _pallas_vmem_limits(jaxpr, found=None) -> dict:
    """``vmem_limit_bytes`` of every named Pallas call in ``jaxpr``, the
    nested ones (a ``custom_vjp``'s) included."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = (
                eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _pallas_vmem_limits(inner, found)
    return found


def test_flash_one_pass_past_the_default_share_equals_the_split(monkeypatch):
    """The same shapes — one D = 128 head of the looped cell's kind at
    T = 2048, three bfloat16 arrays — under today's budget (``VMEM_LIMIT``
    held to ``VMEM_BUDGET``: the two kernels) and under what a call may ask
    for (one pass, its call carrying the plan's bytes as the limit): the
    same output, and gradients equal to one unit in bfloat16's last place
    (the split does its diagonal tiles whole and rounds dS once a kernel;
    nothing else differs)."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    T, D = 2048, 128
    rng = np.random.default_rng(42)
    q, k, v = (jnp.asarray(rng.normal(size=(1, T, D)), jnp.bfloat16)
               for _ in range(3))
    w = jnp.asarray(rng.normal(size=(1, T, D)), jnp.float32)
    attend = lambda q, k, v: FA.flash_attention_btd(q, k, v, heads=1,
                                                    interpret=True)
    loss = lambda q, k, v: (attend(q, k, v).astype(jnp.float32) * w).sum()

    def run(limit):
        monkeypatch.setattr(FA, "VMEM_LIMIT", limit)
        plan = FA.plan_flash(T, T, D, 2, heads=1, layout="btd")
        limits = _pallas_vmem_limits(
            jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v).jaxpr)
        return plan, limits, (attend(q, k, v),
                              *jax.grad(loss, (0, 1, 2))(q, k, v))

    limit = FA.VMEM_LIMIT
    split, split_limits, want = run(FA.VMEM_BUDGET)
    fused, fused_limits, got = run(limit)
    assert not split.fused_bwd and split.bwd_vmem_bytes == 0
    assert set(split_limits) == {
        "penroz_flash_fwd", "penroz_flash_bwd_delta", "penroz_flash_bwd_dq",
        "penroz_flash_bwd_dkv"} and not any(split_limits.values())
    assert fused.fused_bwd and (fused.bwd_diag_grain, split.bwd_diag_grain,
                                fused.bwd_computed_over_live) == (
        128, 512, 1.0625)
    assert FA.VMEM_BUDGET < fused.bwd_vmem_bytes <= 16 * 2 ** 20
    # asked for: the estimate and Mosaic's share, over the default's 16 MiB
    assert fused_limits == {"penroz_flash_fwd": None,
                            "penroz_flash_bwd_delta": None,
                            "penroz_flash_bwd": fused.bwd_vmem_bytes * 4 // 3}
    # the forward is not the limit's: the same plan, the same call
    assert dataclasses.replace(
        fused, fused_bwd=False, bwd_vmem_bytes=0, bwd_diag_grain=512,
        bwd_computed_over_live=split.bwd_computed_over_live) == split
    for a, b in zip(got, want):
        assert _ulps_bf16(a, b).max() <= 1.0


@pytest.mark.parametrize("shape", ["d64_pairs", "d128_gqa"])
def test_flash_btd_chunked_plan_matches_oracle(shape):
    """The streamed forward and the two-kernel backward a small budget
    forces, in the (B, T, lanes) layout, with a window's clamped walks."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    B, Hq, Hkv, _, D = _BTD_SHAPES[shape]
    T = 512
    plan = FA.plan_flash(T, T, D, 4, True, 200, heads=Hq, group=Hq // Hkv,
                         block_q=128, block_k=128, vmem_budget=1,
                         layout="btd")
    assert not plan.resident and not plan.fused_bwd
    rng = np.random.default_rng(3)
    qkv = jnp.asarray(rng.normal(size=(B, T, (Hq + 2 * Hkv) * D))
                      .astype(np.float32))
    w = jnp.asarray(rng.normal(size=(B, T, Hq * D)).astype(np.float32))
    attend = lambda x: FA.flash_attention_btd(
        x, heads=Hq, kv_heads=Hkv, block_q=128, block_k=128, window=200,
        vmem_budget=1, interpret=True)
    want = _btd_oracle(lambda q, k, v: A.causal_attention_reference(
        q, k, v, window=200), Hq, Hkv, D)
    np.testing.assert_allclose(np.asarray(attend(qkv)),
                               np.asarray(want(qkv)), atol=2e-5)
    got_g = jax.grad(lambda x: (attend(x) * w).sum())(qkv)
    want_g = jax.grad(lambda x: (want(x) * w).sum())(qkv)
    _grad_close(_btd_parts(got_g, Hq, Hkv, D),
                _btd_parts(want_g, Hq, Hkv, D))


@pytest.mark.parametrize("D,heads,kv_heads,takes", [
    (64, 12, 12, True),        # the benchmark cell: head pairs
    (128, 32, 8, True),        # any group once a head fills a block
    (256, 8, 1, True),
    (64, 3, 3, False),         # an odd head count leaves half a block
    (64, 4, 2, False),         # GQA at D = 64: K/V heads at other lanes
    (96, 4, 4, False),
])
def test_flash_btd_refusal_names_the_shapes_it_does_not_take(D, heads,
                                                              kv_heads, takes):
    from penroz_tpu.ops.pallas import flash_attention as FA
    why = FA.btd_refusal(D, heads, kv_heads)
    assert (why is None) == takes, why
    if not takes and D in (64, 128, 256):
        with pytest.raises(ValueError, match="flash_attention_btd"):
            FA.flash_attention_btd(
                jnp.zeros((1, 128, (heads + 2 * kv_heads) * D)),
                heads=heads, kv_heads=kv_heads, interpret=True)


def test_flash_plan_names_its_layout(caplog):
    """The plan's counter carries the layout: ``layout=btd
    heads_per_block=2 fused_qkv`` on the INFO line and in the span of a
    cell-shaped call, ``layout=bhtd`` for a (B, H, T, D) caller."""
    import logging
    from penroz_tpu.ops.pallas import flash_attention as FA
    from penroz_tpu.utils import tracing
    plan = FA.plan_flash(1024, 1024, 64, 2, heads=12, layout="btd",
                         fused_qkv=True)
    assert (plan.layout, plan.heads_per_block, plan.heads_per_step,
            plan.fused_qkv) == ("btd", 2, 2, True)
    assert plan.resident and plan.fused_bwd and plan.q_rows == 1024
    assert (plan.diag_grain, plan.computed_over_live) == (256, 1.25)
    assert (plan.bwd_diag_grain, plan.bwd_computed_over_live) == (128, 1.125)
    assert plan.describe().endswith("layout=btd heads_per_block=2 fused_qkv")
    assert FA.plan_flash(1024, 1024, 128, 2, heads=8, group=4,
                         layout="btd").heads_per_block == 1
    old = FA.plan_flash(1024, 1024, 64, 2, heads=12)
    assert old.layout == "bhtd" and old.describe().endswith("layout=bhtd")

    qkv = jnp.zeros((1, 256, 3 * 2 * 64), jnp.float32)
    FA._log_plan.cache_clear()
    tracing.reset()
    trace = tracing.maybe_trace("flash-layout-job", job=True, route="/train/")
    with caplog.at_level(logging.INFO, logger=FA.__name__), \
            tracing.use(trace), tracing.span("penroz/train_dispatch"):
        jax.jit(lambda x: FA.flash_attention_btd(
            x, heads=2, interpret=True)).lower(qkv)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("flash plan:")]
    assert len(lines) == 1 and lines[0].endswith(
        "layout=btd heads_per_block=2 fused_qkv"), lines
    # one 256-tile on the diagonal: whole forward, 3 of its 4 sub-blocks
    # backward, over half of it
    assert (" diag_grain=256 bwd_diag_grain=128 computed_over_live=2.000 "
            "bwd_computed_over_live=1.500 ") in lines[0], lines
    span = trace.to_dict()["root"]["children"][0]["children"][0]
    assert span["name"] == "penroz/flash_plan"
    assert (span["meta"]["diag_grain"], span["meta"]["bwd_diag_grain"],
            span["meta"]["computed_over_live"],
            span["meta"]["bwd_computed_over_live"]) == (256, 128, 2.0, 1.5)
    assert span["meta"]["layout"] == "btd"
    assert span["meta"]["heads_per_block"] == 2
    assert span["meta"]["fused_qkv"] is True
    trace.finish("completed")


def _interpreted_kernels(monkeypatch, calls):
    """Both flash entries in interpret mode, each call noted by layout, and
    the rotation's kernel with them: what ``platform="tpu"`` dispatches to
    on a CPU."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    from penroz_tpu.ops.pallas import rope
    rotate = rope.rotate
    monkeypatch.setattr(rope, "rotate", lambda *args, **kwargs: rotate(
        *args, **kwargs, path="interpret"))
    real = {name: getattr(FA, name)
            for name in ("flash_attention", "flash_attention_btd")}

    def entry(name, layout):
        def run(*args, **kwargs):
            calls.append(layout)
            return real[name](*args, interpret=True, **kwargs)
        return run

    monkeypatch.setattr(FA, "flash_attention", entry("flash_attention",
                                                     "bhtd"))
    monkeypatch.setattr(FA, "flash_attention_btd",
                        entry("flash_attention_btd", "btd"))


_MODULE_CASES = {
    # GPT-2: nothing between the projection and the kernels
    "fused": (dict(num_heads=4), 64),
    "alibi_window": (dict(num_heads=4, alibi=True, sliding_window=100), 64),
    # RoPE at whole heads of 128: the kernel turns the fused projection's q
    # and k where they lie (after a per-head qk-norm, the norm's two arrays);
    # partial RoPE and the norms run on (B, T, H, D) views; three arrays
    "rope_gqa": (dict(num_heads=4, num_kv_heads=2, rope_theta=1e4), 128),
    "partial_rope": (dict(num_heads=2, rope_theta=1e4, rope_pct=0.5), 128),
    "qk_norm_head": (dict(num_heads=2, qk_norm=True, head_dim=128,
                          rope_theta=1e4), 128),
    "qk_norm_flat": (dict(num_heads=4, qk_norm=True, head_dim=64,
                          qk_norm_scope="flat"), 64),
}


@pytest.mark.parametrize("case", list(_MODULE_CASES))
def test_attention_module_paths_agree(case, monkeypatch):
    """``CausalSelfAttention`` on the same weights through the model's own
    layout and through the (B, H, T, D) path it had (PENROZ_DISABLE_FLASH
    aside, a model-axis mesh is what sends a module there): forward and
    the projection's gradient."""
    from penroz_tpu.ops import modules as M
    kwargs, D = _MODULE_CASES[case]
    mod = M.CausalSelfAttention(**kwargs)
    mod.bind("attn")
    heads, kv_heads = mod.num_heads, mod.num_kv_heads
    rng = np.random.default_rng(len(case))
    params = {key: jnp.asarray(1.0 + 0.1 * rng.normal(size=shape),
                               jnp.float32)
              for key, shape in ((mod.key(n), s)
                                 for n, s in mod.param_shapes().items())}
    B, T = 2, 256
    qkv = jnp.asarray(rng.normal(size=(B, T, (heads + 2 * kv_heads) * D))
                      .astype(np.float32))
    w = jnp.asarray(rng.normal(size=(B, T, heads * D)).astype(np.float32))
    calls = []
    _interpreted_kernels(monkeypatch, calls)

    def run(qkv, stay):
        monkeypatch.setattr(A, "stays_in_model_layout",
                            lambda *a, **k: stay)
        return mod.apply(qkv, M.Ctx(params, platform="tpu"))

    new, old = run(qkv, True), run(qkv, False)
    assert calls == ["btd", "bhtd"]
    assert new.shape == (B, T, heads * D)
    np.testing.assert_allclose(np.asarray(new), np.asarray(old), atol=2e-5)
    g_new = jax.grad(lambda x: (run(x, True) * w).sum())(qkv)
    g_old = jax.grad(lambda x: (run(x, False) * w).sum())(qkv)
    _grad_close(_btd_parts(g_new, heads, kv_heads, D),
                _btd_parts(g_old, heads, kv_heads, D))


def test_attention_module_dropout_draws_the_same_mask_on_both_paths(
        monkeypatch):
    from penroz_tpu.ops import modules as M
    mod = M.CausalSelfAttention(num_heads=4, dropout=0.25)
    qkv = jnp.asarray(np.random.default_rng(0).normal(size=(2, 128, 3 * 256))
                      .astype(np.float32))
    calls = []
    _interpreted_kernels(monkeypatch, calls)
    outs = []
    for stay in (True, False):
        monkeypatch.setattr(A, "stays_in_model_layout",
                            lambda *a, **k: stay)
        outs.append(mod.apply(qkv, M.Ctx({}, platform="tpu", training=True,
                                         rng=jax.random.key(7))))
    assert calls == ["btd", "bhtd"]
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               atol=2e-5)
    still = mod.apply(qkv, M.Ctx({}, platform="tpu"))
    assert float(jnp.abs(outs[0] - still).max()) > 0.01


@pytest.mark.parametrize("why", ["none", "cache", "sp_mesh", "sp_axis",
                                 "softcap", "odd_heads", "gqa_d64",
                                 "model_mesh", "data_mesh", "cpu",
                                 "short"])
def test_attention_module_falls_back_to_the_old_path(why, monkeypatch,
                                                     cpu_devices):
    """Each condition the module observes, one at a time: ``none`` and a
    ``data``-only mesh stay in the model's layout, everything else takes
    the path it took before — and a module the kernels would serve that
    still falls back says why, once."""
    import logging
    from penroz_tpu.ops import kv_cache as KV
    from penroz_tpu.ops import modules as M
    from penroz_tpu.parallel import mesh as mesh_lib
    heads, kv_heads, D, T = 4, 4, 64, 128
    kwargs, ctx_kwargs, warns = {}, {"platform": "tpu"}, None
    if why == "cache":
        ctx_kwargs["kv"] = KV.KVState.create([(kv_heads, D)], batch=1,
                                             max_len=T)
    elif why == "sp_mesh":
        ctx_kwargs["sp_mesh"] = mesh_lib.make_mesh(cpu_devices[:2],
                                                   sequence=2)
    elif why == "sp_axis":
        ctx_kwargs["sp_manual_axis"] = "sequence"
    elif why == "softcap":
        kwargs["logit_softcap"] = 30.0    # causal_attention's own warning
    elif why == "odd_heads":
        heads = kv_heads = 3
        warns = "whole 128-lane blocks"
    elif why == "gqa_d64":
        kv_heads, warns = 2, "grouped-query"
    elif why == "model_mesh":
        ctx_kwargs["platform"] = A.Placement("tpu", mesh_lib.make_mesh(
            cpu_devices[:2], model=2))
        warns = "model axis"
    elif why == "data_mesh":
        ctx_kwargs["platform"] = A.Placement("tpu", mesh_lib.make_mesh(
            cpu_devices[:2], model=1))
    elif why == "cpu":
        ctx_kwargs["platform"] = "cpu"
    elif why == "short":
        T = 64
    mod = M.CausalSelfAttention(num_heads=heads, num_kv_heads=kv_heads,
                                **kwargs)
    mod.bind("attn")
    taken = []
    monkeypatch.setattr(mod, "_apply_in_model_layout",
                        lambda qkv, ctx, head_dim: taken.append(head_dim))
    # the old path's branches are not run here, only chosen
    for name in ("causal_attention", "cached_attention"):
        monkeypatch.setattr(A, name, lambda q, *a, **k: q)
    from penroz_tpu.parallel import ring_attention as ring
    monkeypatch.setattr(ring, "ring_attention", lambda q, *a, **k: q)
    monkeypatch.setattr(ring, "ring_attention_manual", lambda q, *a, **k: q)
    monkeypatch.setattr(A, "_WARNED_ONCE", set())
    warned = []
    monkeypatch.setattr(logging.getLogger("penroz_tpu.ops.attention"),
                        "warning", lambda msg, *a: warned.append(msg % a))
    qkv = jnp.zeros((1, T, (heads + 2 * kv_heads) * D), jnp.float32)
    apply = lambda x: mod.apply(x, M.Ctx({}, **ctx_kwargs))
    if why == "sp_axis":    # the old path asks the bound axis for its size
        from jax.sharding import PartitionSpec as P
        apply = jax.shard_map(
            apply, mesh=mesh_lib.make_mesh(cpu_devices[:2], sequence=2),
            in_specs=P(), out_specs=P(), check_vma=False)
    for _ in range(2):
        apply(qkv)
    assert taken == ([D, D] if why in ("none", "data_mesh") else [])
    if warns is None:
        assert warned == []
    else:
        assert len(warned) == 1 and warns in warned[0], warned
        assert "leaves the (B, T, H·D) layout" in warned[0]


def test_attention_module_stays_in_model_layout_under_a_data_mesh(
        monkeypatch, cpu_devices):
    """A ``data``-only mesh splits the batch of the fused projection and
    each shard runs the ``btd`` kernels on its rows (``_on_shards``): the
    same output and gradient as on one device."""
    from penroz_tpu.ops import modules as M
    from penroz_tpu.parallel import mesh as mesh_lib
    mesh = mesh_lib.make_mesh(cpu_devices[:2], model=1)
    mod = M.CausalSelfAttention(num_heads=2)
    rng = np.random.default_rng(5)
    qkv = jnp.asarray(rng.normal(size=(2, 128, 3 * 128)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(2, 128, 128)).astype(np.float32))
    calls = []
    _interpreted_kernels(monkeypatch, calls)

    def loss(platform):
        return lambda x: (mod.apply(x, M.Ctx({}, platform=platform))
                          * w).sum()

    want = jax.value_and_grad(loss("tpu"))(qkv)
    got = jax.jit(jax.value_and_grad(loss(A.Placement("tpu", mesh))))(qkv)
    assert calls and set(calls) == {"btd"}
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# YaRN frequencies, the per-head output gate, heads held as a share
# ---------------------------------------------------------------------------

LAGUNA_FULL = {"rope_type": "yarn", "factor": 128, "beta_fast": 32,
               "beta_slow": 1, "original_max_position_embeddings": 8192,
               "attention_factor": 1.4852030263919618}


def test_yarn_cos_sin_match_the_published_formula_for_lagunas_parameters():
    """``rope_cos_sin`` with ``rope_type: yarn`` against the formula written
    out here (Peng et al. 2023 as published implementations compute it) for
    Laguna-S-2.1's full-attention layers: 64 rotated dims of a 128-wide head
    (``partial_rotary_factor`` 0.5), θ 500 000, factor 128 over 8192, ramp
    between the dims that make 32 and 1 turns, cos and sin × 1.4852."""
    import math
    from penroz_tpu.ops import attention as attn_ops
    dim, theta, length = 64, 500000.0, 40
    s = LAGUNA_FULL
    pair_of = lambda turns: dim * math.log(
        s["original_max_position_embeddings"] / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(pair_of(s["beta_fast"])), 0)
    high = min(math.ceil(pair_of(s["beta_slow"])), dim - 1)
    inv = []
    for i in range(dim // 2):
        plain = theta ** (-2.0 * i / dim)
        ramp = min(1.0, max(0.0, (i - low) / (high - low)))
        inv.append(plain / s["factor"] * ramp + plain * (1.0 - ramp))
    assert 0 < low < high < dim // 2    # all three regimes among the 32 pairs
    assert inv[0] == 1.0 and inv[-1] == pytest.approx(
        theta ** (-62 / 64) / 128)
    positions = np.arange(7, 7 + length)[:, None] * np.asarray(inv)[None, :]
    want_cos = s["attention_factor"] * np.cos(
        np.concatenate([positions, positions], -1))
    want_sin = s["attention_factor"] * np.sin(
        np.concatenate([positions, positions], -1))
    cos, sin = attn_ops.rope_cos_sin(dim, theta, 7, length, jnp.float32,
                                     scaling=attn_ops.yarn_scaling(s))
    np.testing.assert_allclose(cos, want_cos, atol=2e-5)
    np.testing.assert_allclose(sin, want_sin, atol=2e-5)
    # the default attention factor is the published 0.1 ln(factor) + 1
    bare = {k: v for k, v in s.items() if k != "attention_factor"}
    assert attn_ops.yarn_scaling(bare)["attention_factor"] == pytest.approx(
        s["attention_factor"], rel=1e-12)
    # and the benchmark's reference computes the same frequencies
    from benchmark.reference import laguna
    np.testing.assert_allclose(
        laguna.yarn_inv_freq(dim, theta, 128.0, 8192.0, 32.0, 1.0), inv,
        rtol=1e-6)


def _attention_block(heads, kv_heads, head_dim, **kw):
    from penroz_tpu.ops.modules import CausalSelfAttention, Ctx
    mod = CausalSelfAttention(num_heads=heads, num_kv_heads=kv_heads,
                              head_dim=head_dim, **kw)
    return lambda fused: mod.apply(fused, Ctx({}))


def test_per_head_gate_scales_each_heads_output_by_its_sigmoid():
    heads, kv, hd, T = 4, 2, 8, 12
    rng = np.random.default_rng(0)
    qkv = jnp.asarray(rng.normal(size=(2, T, (heads + 2 * kv) * hd)),
                      jnp.float32)
    logits = jnp.asarray(rng.normal(size=(2, T, heads)), jnp.float32)
    plain = _attention_block(heads, kv, hd, rope_theta=1e4)(qkv)
    gated = _attention_block(heads, kv, hd, rope_theta=1e4,
                             gate="per_head")(
        jnp.concatenate([qkv, logits], -1))
    want = (plain.reshape(2, T, heads, hd)
            * jax.nn.sigmoid(logits)[..., None]).reshape(2, T, heads * hd)
    np.testing.assert_allclose(gated, want, atol=1e-6)
    with pytest.raises(ValueError, match="gate"):
        _attention_block(heads, kv, hd, gate="per_token")


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_head_shares_add_up_to_the_uncut_reference_attention_block(kind):
    """Four ranks, each holding one K/V head with its three query heads
    (the fused projection's columns, gate logits included, and the output
    projection's rows cut to them): the program's attention block on each
    share, summed, is the uncut attention block of
    ``benchmark/reference/laguna.py`` (12 query heads on 4 K/V heads; YaRN
    on half a head in the full layer, a window in the sliding one)."""
    from benchmark.reference import laguna
    d, hd, kv, group, T, window = 24, 16, 4, 3, 20, 6
    heads = kv * group
    rope = {"full_attention": {"rope_theta": 500000.0,
                               "partial_rotary_factor": 0.5, **LAGUNA_FULL},
            "sliding_attention": {"rope_theta": 10000.0, "rope_type":
                                  "default", "partial_rotary_factor": 1}}
    keys = jax.random.split(jax.random.key(2), 3)
    fused_w = 0.3 * jax.random.normal(keys[0],
                                      (d, (heads + 2 * kv) * hd + heads))
    o_w = 0.3 * jax.random.normal(keys[1], (heads * hd, d))
    a = jax.random.normal(keys[2], (2, T, d))
    hyper = {"head_dim": hd, "kv_heads": kv, "eps": 1e-6, "window": window,
             "rope": tuple((k, tuple(sorted(v.items())))
                           for k, v in rope.items())}
    with jax.default_matmul_precision("highest"):
        # the reference's layer with the norms' gains at 1 on an input
        # already normed, less its residual and its MLP: run it for the
        # attention branch alone by handing it a zero dense MLP
        ones, zero = jnp.ones((d,)), jnp.zeros((d, 4))
        layer = {"n1": ones, "n2": ones, "qkvg_w": fused_w, "o_w": o_w,
                 "gate_proj": zero, "up_proj": zero, "down_proj": zero.T}
        normed = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-6)
        want = laguna._layer(layer, a, heads=heads, kind=kind, hyper=hyper,
                             mm=jnp.matmul) - a
        spec = rope[kind]
        args = {"rope_theta": spec["rope_theta"], "gate": "per_head"}
        if kind == "full_attention":
            args.update(rope_pct=0.5, rope_scaling=LAGUNA_FULL)
        else:
            args.update(sliding_window=window)
        q0, k0, v0, g0 = 0, heads * hd, (heads + kv) * hd, (heads + 2 * kv) * hd
        total = 0.0
        for rank in range(kv):
            cols = np.r_[q0 + rank * group * hd:q0 + (rank + 1) * group * hd,
                         k0 + rank * hd:k0 + (rank + 1) * hd,
                         v0 + rank * hd:v0 + (rank + 1) * hd,
                         g0 + rank * group:g0 + (rank + 1) * group]
            out = _attention_block(group, 1, hd, **args)(normed @ fused_w[:, cols])
            total = total + out @ o_w[rank * group * hd:(rank + 1) * group * hd]
    np.testing.assert_allclose(total, want, atol=2e-5)


# -- unlike score and value widths (latent attention) -------------------------

@pytest.mark.parametrize("plan_name", ["resident128", "resident256",
                                       "chunked128"])
@pytest.mark.parametrize("widths", [(192, 128), (48, 32), (64, 64)],
                         ids=["192x128", "48x32", "64x64"])
def test_flash_takes_scores_and_values_of_unlike_widths(widths, plan_name):
    """q, k ``D`` wide and v, o ``Dv`` wide (interpret): forward and
    dq/dk/dv against the jnp path under the resident forward with the
    one-pass backward and under the chunked kernels with the split one, with
    a scale of the caller's; beside the same at ``D == Dv``."""
    from penroz_tpu.ops.pallas import flash_attention as FA
    D, Dv = widths
    block_q, block_k, budget = _FLASH_PLANS[plan_name]
    rng = np.random.default_rng(D + len(plan_name))
    draw = lambda *shape: jnp.asarray(
        rng.normal(size=shape).astype(np.float32))
    q, k, v = draw(1, 4, 256, D), draw(1, 2, 256, D), draw(1, 2, 256, Dv)
    extra = {} if budget is None else {"vmem_budget": budget}
    plan = FA.plan_flash(256, 256, D, 4, heads=4, group=2, block_q=block_q,
                         block_k=block_k, Dv=Dv, **extra)
    assert plan.layout == "bhtd"
    assert (plan.resident, plan.fused_bwd) == ((budget is None,) * 2)

    def attend(q, k, v):
        return FA.flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                                  interpret=True, scale=0.11, **extra)

    oracle = lambda q, k, v: A.causal_attention_reference(q, k, v,
                                                          scale=0.11)
    out = attend(q, k, v)
    assert out.shape == (1, 4, 256, Dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle(q, k, v)),
                               atol=2e-5)
    w = draw(*out.shape)
    gf = jax.grad(lambda q, k, v: (attend(q, k, v) * w).sum(),
                  (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: (oracle(q, k, v) * w).sum(),
                  (0, 1, 2))(q, k, v)
    assert [g.shape for g in gf] == [q.shape, k.shape, v.shape]
    _grad_close(gf, gr)


def test_flash_plan_at_equal_widths_is_what_it_was_and_names_unlike_ones(
        caplog):
    """``Dv`` left out, or equal to ``D``, changes no plan; the plan line
    names ``Dv`` only where it differs; the ``btd`` layout refuses the
    pair and says which."""
    import logging
    from penroz_tpu.ops.pallas import flash_attention as FA
    for args, kw in (((1024, 1024, 64, 2), dict(heads=12, layout="btd",
                                                fused_qkv=True)),
                     ((4096, 4096, 128, 2), dict(heads=16, layout="btd")),
                     ((8192, 8192, 128, 2), dict(heads=6, group=6))):
        assert FA.plan_flash(*args, **kw) == FA.plan_flash(
            *args, Dv=args[2], **kw)
    plan = FA.plan_flash(4096, 4096, 192, 2, heads=32, Dv=128)
    assert plan.fused_bwd and plan.layout == "bhtd"
    assert plan.bwd_vmem_bytes < FA.plan_flash(4096, 4096, 192, 2,
                                               heads=32).bwd_vmem_bytes
    FA._log_plan.cache_clear()
    with caplog.at_level(logging.INFO, logger=FA.log.name):
        FA._record_plan(4096, 4096, 192, plan, 128)
        FA._record_plan(4096, 4096, 128, plan)
    lines = [r.getMessage() for r in caplog.records]
    assert lines[0].startswith("flash plan: T=4096 S=4096 D=192 Dv=128 bq=")
    assert lines[1].startswith("flash plan: T=4096 S=4096 D=128 bq=")
    assert "(D, Dv)=(192, 128)" in FA.btd_refusal(192, 32, 32, 128)
    with pytest.raises(ValueError, match="one head width"):
        FA.plan_flash(4096, 4096, 192, 2, heads=32, Dv=128, layout="btd")


def test_a_shape_off_the_flash_kernels_says_so_once(monkeypatch, caplog):
    """On a TPU, a training shape the kernels do not take goes to the jnp
    path with one log line that names the refused pair (D, Dv)."""
    import logging
    monkeypatch.setattr(A, "_WARNED_ONCE", set())
    q = jnp.zeros((1, 2, 128, 96))
    v = jnp.zeros((1, 2, 128, 64))
    assert A._flash_shapes(4096, 192, 32, 32, 128)
    assert A._flash_shapes(1024, 64, 12, 12)
    assert not A._flash_shapes(4096, 192, 32, 32)       # 192-wide values
    with caplog.at_level(logging.WARNING, logger=A.log.name):
        assert not A._use_flash(q, q, "tpu", v)
        assert not A._use_flash(q, q, "tpu", v)
    said = [r.getMessage() for r in caplog.records
            if "leaves the flash kernels" in r.getMessage()]
    assert len(said) == 1 and "(D, Dv)=(96, 64)" in said[0]
    assert not A._use_flash(q, q, "cpu", v)             # no TPU: no line
