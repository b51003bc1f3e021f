"""ops/pallas/rope.py: the rotation of q and k where they lie, against
``ops/attention.py::apply_rope`` written out plainly.

On the CPU the kernel runs interpreted (``path='interpret'``) and its formula
in ``jax.numpy`` (``path='jnp'``); that the chip's compiler takes it at the
cells' shapes is tests/test_tpu_compile.py's, and what it costs there
PERF.md §6 (PR 49).
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from penroz_tpu.ops import attention as A
from penroz_tpu.ops import modules as M
from penroz_tpu.ops.pallas import rope

THETA = 10000.0


def _plain(x, cos, sin):
    """``x`` ``(B, T, H, D)`` rotated: x · cos + rotate_half(x) · sin."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    expand = lambda t: t[..., None, :]      # the heads' axis
    return x * expand(cos) + turned * expand(sin)


def _reference(qkv, cos, sin, heads, kv_heads, D):
    """(q_rot, k_rot, v) of a fused ``(B, T, (heads + 2·kv_heads)·D)``
    projection, tables in the projection's type as the parent had them."""
    B, T, _ = qkv.shape
    q_dim, kv_dim = heads * D, kv_heads * D
    cos, sin = cos.astype(qkv.dtype), sin.astype(qkv.dtype)
    q = _plain(qkv[..., :q_dim].reshape(B, T, heads, D), cos, sin)
    k = _plain(qkv[..., q_dim:q_dim + kv_dim].reshape(B, T, kv_heads, D),
               cos, sin)
    return (q.reshape(B, T, q_dim), k.reshape(B, T, kv_dim),
            qkv[..., q_dim + kv_dim:])


def _apply_rope(qkv, heads, kv_heads, D, offset=0, scaling=None):
    """(q_rot, k_rot, v) by ``apply_rope`` itself on head-split views."""
    B, T, _ = qkv.shape
    q_dim, kv_dim = heads * D, kv_heads * D
    q, k = A.apply_rope(
        qkv[..., :q_dim].reshape(B, T, heads, D),
        qkv[..., q_dim:q_dim + kv_dim].reshape(B, T, kv_heads, D),
        THETA, offset, scaling=scaling, seq_axis=1)
    return (q.reshape(B, T, q_dim), k.reshape(B, T, kv_dim),
            qkv[..., q_dim + kv_dim:])


def _inputs(B, T, heads, kv_heads, D, dtype=jnp.float32, seed=0):
    width = (heads + 2 * kv_heads) * D
    qkv = jax.random.normal(jax.random.key(seed), (B, T, width), jnp.float32)
    weights = jax.random.normal(jax.random.key(seed + 1), (B, T, width),
                                jnp.float32)
    return qkv.astype(dtype), weights


def _weighted(fn, weights):
    """A scalar of every lane of ``fn``'s results, each with its own
    weight, in float32."""
    def loss(*args):
        out = jnp.concatenate([o.astype(jnp.float32) for o in fn(*args)],
                              axis=-1)
        return (out * weights[..., :out.shape[-1]]).sum()
    return loss


def _worst(got, want):
    return max(float(jnp.abs(g.astype(jnp.float32) - w).max())
               for g, w in zip(got, want))


@pytest.mark.parametrize("heads,kv_heads,D", [
    (16, 16, 128), (9, 1, 128), (6, 1, 128), (4, 2, 256), (2, 2, 256)],
    ids=["16on16_d128", "9on1_d128", "6on1_d128", "4on2_d256", "2on2_d256"])
@pytest.mark.parametrize("path", ["interpret", "jnp"])
def test_rotation_and_its_gradient_match_apply_rope(path, heads, kv_heads, D):
    """float32 to 1e-5, values and gradients, equal and grouped head counts,
    one register width a head and two: the fused projection split into
    (q_rot, k_rot, v) with the projection's own cotangent back, and q and k
    as two arrays."""
    B, T = 2, 128
    qkv, weights = _inputs(B, T, heads, kv_heads, D)
    cos, sin = A.rope_cos_sin(D, THETA, 0, T, jnp.float32)
    q_dim, kv_dim = heads * D, kv_heads * D
    want = _reference(qkv, cos, sin, heads, kv_heads, D)
    fused = lambda x: rope.rotate(x, None, cos, sin, heads=heads,
                                  kv_heads=kv_heads, path=path)
    got = fused(qkv)
    assert [g.shape for g in got] == [(B, T, q_dim), (B, T, kv_dim),
                                      (B, T, kv_dim)]
    assert _worst(got, want) < 1e-5
    grad = jax.grad(_weighted(fused, weights))(qkv)
    grad_want = jax.grad(_weighted(
        lambda x: _reference(x, cos, sin, heads, kv_heads, D), weights))(qkv)
    assert grad.shape == qkv.shape
    assert _worst([grad], [grad_want]) < 1e-5

    apart = lambda q, k: rope.rotate(q, k, cos, sin, heads=heads,
                                     kv_heads=kv_heads, path=path)
    q, k = qkv[..., :q_dim], qkv[..., q_dim:q_dim + kv_dim]
    assert _worst(apart(q, k), want[:2]) < 1e-5
    dq, dk = jax.grad(_weighted(apart, weights), argnums=(0, 1))(q, k)
    assert _worst([dq, dk], [grad_want[..., :q_dim],
                             grad_want[..., q_dim:q_dim + kv_dim]]) < 1e-5


def test_the_plain_reference_is_apply_rope():
    B, T, heads, kv_heads, D = 2, 128, 4, 2, 128
    qkv, _ = _inputs(B, T, heads, kv_heads, D)
    cos, sin = A.rope_cos_sin(D, THETA, 0, T, jnp.float32)
    assert _worst(_reference(qkv, cos, sin, heads, kv_heads, D),
                  _apply_rope(qkv, heads, kv_heads, D)) == 0.0


@pytest.mark.parametrize("heads,kv_heads,D", [(16, 16, 128), (4, 2, 256)],
                         ids=["16on16_d128", "4on2_d256"])
def test_bfloat16_is_no_worse_than_the_parents_own_error(heads, kv_heads, D):
    """The parent multiplies in bfloat16 by bfloat16 tables; the kernel
    widens in registers, multiplies by float32 tables and rounds once.
    Against the rotation of the same bfloat16 inputs in float32, its values
    and its gradient are at least as near as the parent's, worst lane and
    mean."""
    B, T = 2, 256
    qkv, weights = _inputs(B, T, heads, kv_heads, D, jnp.bfloat16, seed=3)
    cos, sin = A.rope_cos_sin(D, THETA, 0, T, jnp.float32)
    exact = lambda x: _reference(x.astype(jnp.float32), cos, sin, heads,
                                 kv_heads, D)
    parent = lambda x: _reference(x, cos, sin, heads, kv_heads, D)
    kernel = lambda x: rope.rotate(x, None, cos, sin, heads=heads,
                                   kv_heads=kv_heads, path="interpret")
    truth = jnp.concatenate(exact(qkv), axis=-1)
    grad_truth = jax.grad(_weighted(exact, weights))(qkv).astype(jnp.float32)

    def errors(fn):
        values = jnp.concatenate(fn(qkv), axis=-1)
        assert values.dtype == jnp.bfloat16
        grad = jax.grad(_weighted(fn, weights))(qkv)
        assert grad.dtype == jnp.bfloat16
        off = jnp.abs(values.astype(jnp.float32) - truth)
        grad_off = jnp.abs(grad.astype(jnp.float32) - grad_truth)
        return [float(x) for x in (off.max(), off.mean(), grad_off.max(),
                                   grad_off.mean())]

    ours, theirs = errors(kernel), errors(parent)
    assert all(a <= b for a, b in zip(ours, theirs)), (ours, theirs)
    assert ours[1] < theirs[1] and ours[3] < theirs[3], (ours, theirs)


@pytest.mark.parametrize("offset", [
    37, np.array([0, 5]), np.arange(2 * 128).reshape(2, 128)[:, ::-1] * 3],
    ids=["scalar", "per_row", "per_token"])
def test_offsets_are_rope_cos_sins(offset):
    """A scalar offset shifts the one ``(T, D)`` table; a ``(B,)`` or ``(B,
    T)`` one gives every row its own ``(B, T, D)`` table, which the kernel
    reads a row at a time."""
    B, T, heads, kv_heads, D = 2, 128, 4, 2, 128
    qkv, weights = _inputs(B, T, heads, kv_heads, D, seed=5)
    cos, sin = A.rope_cos_sin(D, THETA, jnp.asarray(offset), T, jnp.float32)
    assert cos.ndim == (2 if np.ndim(offset) == 0 else 3)
    want = lambda x: _apply_rope(x, heads, kv_heads, D, jnp.asarray(offset))
    got = lambda x: rope.rotate(x, None, cos, sin, heads=heads,
                                kv_heads=kv_heads, path="interpret")
    assert _worst(got(qkv), want(qkv)) < 1e-5
    assert _worst([jax.grad(_weighted(got, weights))(qkv)],
                  [jax.grad(_weighted(want, weights))(qkv)]) < 1e-5


@pytest.mark.parametrize("scaling", [
    {"rope_type": "yarn", "factor": 128.0, "beta_fast": 32.0,
     "beta_slow": 1.0, "original_max_position_embeddings": 8192.0,
     "attention_factor": 1.4852030263919618},
    {"rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
     "high_freq_factor": 4.0, "original_max_position_embeddings": 64.0},
    {"rope_type": "linear", "factor": 8.0}],
    ids=["yarn_amplitude", "llama3", "linear"])
def test_a_scaled_table_is_taken_as_it_is(scaling):
    """Frequencies and amplitude are ``rope_cos_sin``'s: YaRN's table is
    1.485 times a rotation, and the backward turns back by the same."""
    B, T, heads, kv_heads, D = 1, 256, 2, 1, 128
    qkv, weights = _inputs(B, T, heads, kv_heads, D, seed=9)
    cos, sin = A.rope_cos_sin(D, THETA, 0, T, jnp.float32, scaling=scaling)
    if scaling["rope_type"] == "yarn":
        assert float(cos[0, 0]) == pytest.approx(1.4852030263919618)
    want = lambda x: _apply_rope(x, heads, kv_heads, D, scaling=scaling)
    for path in ("interpret", "jnp"):
        got = lambda x: rope.rotate(x, None, cos, sin, heads=heads,
                                    kv_heads=kv_heads, path=path)
        assert _worst(got(qkv), want(qkv)) < 1e-5
        assert _worst([jax.grad(_weighted(got, weights))(qkv)],
                      [jax.grad(_weighted(want, weights))(qkv)]) < 1e-5


@pytest.mark.parametrize("tokens,head_dim,rotary_dim,want", [
    (4096, 128, None, True), (8192, 256, 256, True), (128, 128, 128, True),
    (8192, 128, 64, False), (4096, 64, None, False),
    (4096, 192, None, False), (4096 - 8, 128, None, False)],
    ids=["cell", "d256", "one_tile", "partial_rotary", "d64", "d192",
         "ragged_tiles"])
def test_fits_says_who_may_come(tokens, head_dim, rotary_dim, want):
    assert rope.fits(tokens, head_dim, rotary_dim) is want


def _attention(heads=16, kv_heads=16, **kwargs):
    mod = M.CausalSelfAttention(num_heads=heads, num_kv_heads=kv_heads,
                                rope_theta=1e6, **kwargs)
    mod.bind("attn")
    return mod


def _meshed():
    from jax.sharding import Mesh
    return A.Placement("tpu", Mesh(np.array(jax.devices()[:2]), ("data",)))


@pytest.mark.parametrize("case,want", [
    ("one_tpu", "kernel"), ("d256", "kernel"), ("qk_norm", "kernel"),
    ("mesh", "xla"), ("cpu", "xla"), ("partial_rotary", "xla"),
    ("d64", "xla"), ("cache", None)])
def test_who_takes_the_kernel_is_decided_from_what_is_seen(case, want,
                                                           monkeypatch):
    """A traced layer's ``penroz/rope_plan`` span: the kernel on one TPU in
    the ``(B, T, H·D)`` layout at whole heads of 128 or 256 rotated dims
    (after a per-head qk-norm too); ``apply_rope`` under a mesh, off the
    TPU, at partial rotary and at D = 64; with a KV cache no record at all.
    Nothing runs: the layer is traced for its shapes."""
    from penroz_tpu.ops import kv_cache as KV
    from penroz_tpu.utils import tracing
    heads, kv_heads, D, T = 4, 2, 128, 256
    kwargs, ctx_kwargs = {}, {"platform": "tpu"}
    if case == "d256":
        D = 256
    elif case == "qk_norm":
        kwargs.update(qk_norm=True, head_dim=D)
    elif case == "mesh":
        ctx_kwargs["platform"] = _meshed()
    elif case == "cpu":
        ctx_kwargs["platform"] = "cpu"
    elif case == "partial_rotary":
        kwargs["rope_pct"] = 0.5
    elif case == "d64":
        D, kv_heads = 64, 4
    elif case == "cache":
        ctx_kwargs["kv"] = KV.KVState.create([(kv_heads, D)], batch=1,
                                             max_len=T)
    mod = _attention(heads, kv_heads, **kwargs)
    params = {mod.key(n): jnp.ones(s, jnp.float32)
              for n, s in mod.param_shapes().items()}
    monkeypatch.setattr(A, "_WARNED_ONCE", set())
    tracing.reset()
    trace = tracing.maybe_trace(f"rope-plan-{case}", job=True,
                                route="/train/")
    qkv = jax.ShapeDtypeStruct((1, T, (heads + 2 * kv_heads) * D),
                               jnp.bfloat16)
    with tracing.use(trace), tracing.span("penroz/train_dispatch"):
        out = jax.eval_shape(
            lambda x: mod.apply(x, M.Ctx(params, **ctx_kwargs)), qkv)
    assert out.shape == (1, T, heads * D)
    dispatch = trace.to_dict()["root"]["children"][0]
    plans = [c["meta"] for c in dispatch.get("children", [])
             if c["name"] == "penroz/rope_plan"]
    trace.finish("completed")
    if want is None:
        assert plans == []
        return
    rotary = D // 2 if case == "partial_rotary" else D
    assert plans == [{"path": want, "heads": heads, "kv_heads": kv_heads,
                      "D": D, "T": T, "rotary_dim": rotary,
                      "bytes": 2 * T * (heads + kv_heads) * D * 2}]


def test_rope_plan_is_logged_once_a_distinct_plan(caplog, monkeypatch):
    """The looped cell's line, letter for letter, as PERF.md §3 quotes it;
    Laguna's two kinds of layer beside it."""
    # the server's log_config.json, once a test of this worker has loaded
    # it, keeps the package's records from the root logger caplog hears
    monkeypatch.setattr(logging.getLogger("penroz_tpu"), "propagate", True)
    M._log_plan.cache_clear()
    cell = _attention()
    sliding = _attention(9, 1, sliding_window=512)
    full = _attention(6, 1, rope_pct=0.5)
    with caplog.at_level(logging.INFO, logger=M.__name__):
        for _ in range(2):
            for mod, shape in ((cell, (2, 4096, 6144)),
                               (sliding, (1, 8192, 1408)),
                               (full, (1, 8192, 1024))):
                x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
                mod._record_rope_plan(x, M.Ctx({}, platform="tpu"), 128,
                                      True)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("rope plan:")]
    assert lines == [
        "rope plan: path=kernel heads=16 kv_heads=16 D=128 T=4096 "
        "rotary_dim=128 bytes=134217728",
        "rope plan: path=kernel heads=9 kv_heads=1 D=128 T=8192 "
        "rotary_dim=128 bytes=41943040",
        "rope plan: path=xla heads=6 kv_heads=1 D=128 T=8192 "
        "rotary_dim=64 bytes=29360128"]


@pytest.mark.parametrize("qk_norm", [False, True],
                         ids=["fused_projection", "after_qk_norm"])
def test_the_layer_hands_the_flash_entry_what_apply_rope_would(qk_norm,
                                                               monkeypatch):
    """``CausalSelfAttention._apply_in_model_layout`` with the kernel
    (interpreted) and with ``apply_rope``: the three arrays the ``btd``
    flash entry is given are the same to float32's rounding, and so is the
    projection's cotangent through them.  Without a norm the fused
    projection goes to the kernel whole; after a per-head qk-norm q and k go
    as the two arrays the norm made and v as the projection's slice."""
    heads, kv_heads, D, T = 4, 2, 128, 128
    mod = _attention(heads, kv_heads, qk_norm=qk_norm, head_dim=D)
    params = {mod.key(n): 1.0 + 0.1 * jax.random.normal(
        jax.random.key(2), s, jnp.float32)
        for n, s in mod.param_shapes().items()}
    qkv, weights = _inputs(2, T, heads, kv_heads, D, seed=11)
    given = []
    monkeypatch.setattr(
        A, "causal_attention_btd",
        lambda *arrays, **kw: given.append(len(arrays)) or jnp.concatenate(
            arrays, axis=-1))
    kernel = rope.rotate
    monkeypatch.setattr(rope, "rotate", lambda *a, **kw: kernel(
        *a, **kw, path="interpret"))
    layer = lambda x: (mod._apply_in_model_layout(
        x, M.Ctx(params, platform="tpu"), D),)
    got, grad = layer(qkv), jax.grad(_weighted(layer, weights))(qkv)
    monkeypatch.setattr(rope, "fits", lambda *a: False)
    want, grad_want = layer(qkv), jax.grad(_weighted(layer, weights))(qkv)
    assert given == [3] * 4
    assert got[0].shape == qkv.shape
    assert _worst(got, want) < 1e-5 and _worst([grad], [grad_want]) < 1e-4
