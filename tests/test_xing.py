"""Latent attention, the multi-stream residual and the sigmoid router with a
selection bias against ``benchmark/reference/xing.py`` (plain ``jax.numpy``),
at small sizes on the CPU with seeded weights: each module alone, then the
whole ``presets.xing_custom`` model's first optimizer step at the benchmark
configuration's ``rehearse`` sizes."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing
from penroz_tpu.models import presets
from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import CompiledArch
from penroz_tpu.ops import modules as M

pytestmark = pytest.mark.runtime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "xing4.0-29b-a4b-ep8-5l.json")


def _rehearse_cfg(**over) -> dict:
    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    cfg.update(over)
    return cfg


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol,
                               rtol=tol)


# -- latent attention --------------------------------------------------------

ROPE = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


@pytest.mark.parametrize("rope", [
    ROPE, {**ROPE, "mscale": 0.7, "mscale_all_dim": 1.3}],
    ids=["mscale_equal", "mscale_unlike"])
def test_latent_attention_matches_the_reference(rope):
    """Forward and every gradient (both norms, the shared rotary key's
    columns of W_kva included); with ``mscale`` ≠ ``mscale_all_dim`` cos and
    sin carry their ratio and the scores' scale ``m(mscale_all_dim)²``."""
    d, H, qr, kr, dn, dr, dv, T = 48, 3, 20, 12, 16, 8, 12, 32
    mod = M.LatentAttention(d, H, qr, kr, dn, dr, dv, rope_scaling=rope)
    mod.bind("a")
    keys = jax.random.split(jax.random.key(3), 9)
    normal = lambda i, *shape: 0.3 * jax.random.normal(keys[i], shape)
    ref = {"q_a": normal(0, d, qr), "q_norm": 1 + normal(1, qr),
           "q_b": normal(2, qr, H * (dn + dr)), "kv_a": normal(3, d, kr + dr),
           "kv_norm": 1 + normal(4, kr), "kv_b": normal(5, kr, H * (dn + dv)),
           "o_w": normal(6, H * dv, d)}
    x = jax.random.normal(keys[7], (2, T, d))
    w = jax.random.normal(keys[8], (2, T, d))
    hyper = {"heads": H, "d_nope": dn, "d_rope": dr, "d_v": dv,
             "kv_rank": kr, "theta": 10000.0, "eps": 1e-6,
             "rope": tuple(sorted(rope.items()))}
    assert mod.softmax_scale == pytest.approx(
        xing.softmax_scale(dn + dr, rope))
    if rope["mscale"] != rope["mscale_all_dim"]:
        assert xing.rope_amplitude(rope) != pytest.approx(1.0)
        assert mod.rope_scaling["attention_factor"] == pytest.approx(
            xing.rope_amplitude(rope))

    def program(ref, x):
        params = {"a.q_a_proj.weight": ref["q_a"].T,
                  "a.q_a_norm.weight": ref["q_norm"],
                  "a.q_b_proj.weight": ref["q_b"].T,
                  "a.kv_a_proj.weight": ref["kv_a"].T,
                  "a.kv_a_norm.weight": ref["kv_norm"],
                  "a.kv_b_proj.weight": ref["kv_b"].T,
                  "a.o_proj.weight": ref["o_w"].T}
        return mod.apply(x, M.Ctx(params, platform="cpu"))

    reference = lambda ref, x: xing._latent_attention(
        ref, x, hyper=hyper, mm=jnp.matmul)
    _close(program(ref, x), reference(ref, x))
    got = jax.grad(lambda r, x: (program(r, x) * w).sum(), (0, 1))(ref, x)
    want = jax.grad(lambda r, x: (reference(r, x) * w).sum(), (0, 1))(ref, x)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(g, r, 1e-4)


def test_latent_attention_refuses_a_cache_with_one_error():
    layers = presets.xing_custom(**xing.preset_args(_rehearse_cfg()))
    arch = CompiledArch.get(layers)
    with pytest.raises(ValueError, match="latent attention does not run"):
        arch.kv_specs


# -- the multi-stream residual -----------------------------------------------

def _mixing(key, n, d):
    k = jax.random.split(key, 4)
    return {"phi": 0.02 * jax.random.normal(k[0], (n * d, 2 * n + n * n)),
            "alpha": jnp.asarray([0.8, 1.1, 0.9]),
            "bias": jnp.concatenate([
                jax.random.normal(k[1], (2 * n,)),
                (jax.random.normal(k[2], (n, n)) + 4 * jnp.eye(n))
                .reshape(-1)])}


def _hc_module(n, d, iters=20, **ends):
    mod = M.HyperConnected(d, M.Sequential(M.RMSNorm(d), M.Linear(d, d,
                                                                  bias=False)),
                           streams=n, sinkhorn_iters=iters, **ends)
    mod.bind("h")
    return mod


def _hc_params(hc, body_w, d):
    return {"h.phi.weight": hc["phi"].T, "h.alpha": hc["alpha"],
            "h.bias": hc["bias"], "h.body.0.weight": jnp.ones((d,)),
            "h.body.1.weight": body_w.T}


def test_hyperconnected_matches_the_reference_through_sinkhorn():
    """Forward, the gradient of Phi, alpha, b, the body and X through all 20
    iterations, and ``hc_sinkhorn_err`` equal to the reference's own."""
    n, d, T = 4, 24, 16
    keys = jax.random.split(jax.random.key(5), 5)
    hc = _mixing(keys[0], n, d)
    body_w = 0.3 * jax.random.normal(keys[1], (d, d))
    X = jax.random.normal(keys[2], (2, T, n, d))
    w = jax.random.normal(keys[3], (2, T, n, d))
    mod = _hc_module(n, d)
    hyper = {"eps": 1e-6, "clamp": (-30.0, 30.0), "sinkhorn_iters": 20,
             "hc_eps": 1e-6}

    def program(hc, body_w, X):
        ctx = M.Ctx(_hc_params(hc, body_w, d), platform="cpu")
        return mod.apply(X, ctx), ctx.reported()["hc_sinkhorn_err"]

    def reference(hc, body_w, X):
        f = lambda u: jnp.matmul(xing._rmsnorm(u, jnp.ones((d,)), 1e-6),
                                 body_w)
        return xing._mixed(hc, X, f, hyper=hyper, mm=jnp.matmul)

    (got, got_err), (want, want_err) = (program(hc, body_w, X),
                                        reference(hc, body_w, X))
    _close(got, want)
    assert float(got_err) == pytest.approx(float(want_err), rel=1e-3,
                                           abs=1e-7)
    grads = [jax.grad(lambda h, b, X: (f(h, b, X)[0] * w).sum(), (0, 1, 2))(
        hc, body_w, X) for f in (program, reference)]
    for g, r in zip(*map(jax.tree.leaves, grads)):
        _close(g, r, 1e-4)


def test_hyperconnected_recomputes_its_sub_block_in_training():
    """In training a sub-block is one ``jax.checkpoint`` (the backward keeps
    its input streams and runs its inside again): the same result, the same
    gradient, and the largest value it noted still reaches the caller."""
    n, d, T = 4, 24, 16
    keys = jax.random.split(jax.random.key(6), 4)
    hc = _mixing(keys[0], n, d)
    body_w = 0.3 * jax.random.normal(keys[1], (d, d))
    X = jax.random.normal(keys[2], (2, T, n, d))
    w = jax.random.normal(keys[3], (2, T, n, d))
    mod = _hc_module(n, d)

    def program(hc, body_w, X, training):
        ctx = M.Ctx(_hc_params(hc, body_w, d), platform="cpu",
                    training=training, rng=jax.random.key(0))
        return mod.apply(X, ctx), ctx.reported()["hc_sinkhorn_err"]

    (got, got_err), (want, want_err) = (program(hc, body_w, X, True),
                                        program(hc, body_w, X, False))
    _close(got, want, 1e-6)
    assert float(got_err) == pytest.approx(float(want_err), rel=1e-6)
    loss = lambda h, b, X, t: (program(h, b, X, t)[0] * w).sum()
    grads = [jax.grad(loss, (0, 1, 2))(hc, body_w, X, t)
             for t in (True, False)]
    for g, r in zip(*map(jax.tree.leaves, grads)):
        _close(g, r, 1e-5)
    names = lambda t: {e.primitive.name for e in jax.make_jaxpr(
        lambda X: program(hc, body_w, X, t)[0])(X).eqns}
    assert "remat2" in names(True) and "remat2" not in names(False)


def test_hyperconnected_maps_are_doubly_stochastic_at_20_iterations():
    n, d = 4, 24
    keys = jax.random.split(jax.random.key(6), 3)
    hc = _mixing(keys[0], n, d)
    X = jax.random.normal(keys[1], (1, 64, n, d))
    mod = _hc_module(n, d)
    ctx = M.Ctx(_hc_params(hc, jnp.eye(d), d), platform="cpu")
    pre, post, res = mod.maps_of(X, ctx)
    assert pre.shape == (n, 64) and res.shape == (n, n, 64)
    # rows end at 1 by construction; the columns come as near as 20
    # iterations bring them on these weights (b_res N(0, 1) + 4 I: a
    # diagonal e^4 above the rest converges slowly; 8e-3 here, which is
    # what the ``hc_sinkhorn_err`` counter is there to say)
    np.testing.assert_allclose(res.sum(1), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(0), 1.0, atol=2e-2)
    flat = {**hc, "bias": hc["bias"].at[2 * n:].set(0.0)}
    even = mod.maps_of(X, M.Ctx(_hc_params(flat, jnp.eye(d), d),
                                platform="cpu"))[2]
    np.testing.assert_allclose(even.sum(0), 1.0, atol=1e-3)
    assert float(post.max()) <= 2.0 and float(pre.max()) <= 1.0
    # fewer iterations leave the columns further from 1
    few = _hc_module(n, d, iters=1).maps_of(X, ctx)[2]
    assert float(jnp.abs(few.sum(0) - 1).max()) \
        > float(jnp.abs(res.sum(0) - 1).max())


def test_hyperconnected_expands_copies_and_reduces_to_their_sum():
    n, d = 4, 24
    keys = jax.random.split(jax.random.key(7), 3)
    hc = _mixing(keys[0], n, d)
    x = jax.random.normal(keys[1], (2, 8, d))
    params = _hc_params(hc, 0.3 * jax.random.normal(keys[2], (d, d)), d)
    X = jnp.broadcast_to(x[:, :, None, :], (2, 8, n, d))
    whole = _hc_module(n, d).apply(X, M.Ctx(params, platform="cpu"))
    _close(_hc_module(n, d, expand=True).apply(
        x, M.Ctx(params, platform="cpu")), whole)
    _close(_hc_module(n, d, reduce=True).apply(
        X, M.Ctx(params, platform="cpu")), whole.sum(2))
    with pytest.raises(ValueError, match="hyperconnected takes"):
        _hc_module(n, d).apply(x, M.Ctx(params, platform="cpu"))


def _plain_mix(mod, X, ctx):
    """The mixing's formulas written out plainly (the module's own until
    PR 48): float32 copies of the streams, the Phi product on ``X`` laid out
    ``(B·T, n·d)``, every map and multiply-add left to autodiff."""
    B, T, n, d = X.shape
    flat = X.reshape(B * T, n * d)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(flat.astype(jnp.float32)),
                               axis=-1) + mod.eps)
    u = jnp.matmul(flat, mod._p(ctx, "phi.weight").T,
                   preferred_element_type=jnp.float32)
    u = (u * r[:, None]).T
    alpha = ctx.params[mod.key("alpha")].astype(jnp.float32)
    bias = ctx.params[mod.key("bias")].astype(jnp.float32)[:, None]
    pre = jax.nn.sigmoid(alpha[0] * u[:n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * u[n:2 * n] + bias[n:2 * n])
    res = jnp.clip(alpha[2] * u[2 * n:] + bias[2 * n:], *mod.res_clamp)
    res = M.sinkhorn(res.reshape(n, n, B * T), mod.sinkhorn_iters,
                     mod.hc_eps)
    col = lambda t: t.reshape(B, T, 1)
    streams = [X[:, :, j, :].astype(jnp.float32) for j in range(n)]
    x_in = sum(col(pre[i]) * streams[i] for i in range(n))
    y = mod.body.apply(x_in.astype(X.dtype), ctx).astype(jnp.float32)
    new = [sum(col(res[i, j]) * streams[j] for j in range(n))
           + col(post[i]) * y for i in range(n)]
    if mod.reduce:
        return sum(new).astype(X.dtype)
    return jnp.stack(new, axis=2).astype(X.dtype)


@pytest.mark.parametrize("path", ["fused", "interpret"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n,ends", [
    (4, {}), (2, {}), (4, {"expand": True}), (4, {"reduce": True}),
    (2, {"expand": True, "reduce": True})],
    ids=["n4", "n2", "n4_expand", "n4_reduce", "n2_expand_reduce"])
def test_hyperconnected_passes_match_the_formulas_written_out(
        monkeypatch, n, ends, dtype, path):
    """The mixing as passes over the state with a hand-written backward
    (``_hc_read`` / ``_hc_write``) against the formulas written out plainly
    in float32: forward and every gradient (the state, the body's weight
    through ``y``, Phi, alpha, the biases).  ``fused``: the passes as XLA
    gets them off the TPU; ``interpret``: the TPU's kernel
    (``ops/pallas/hc_mix.py``) interpreted, at a shape its tiles admit.
    Float32 to 1e-5; bfloat16 state and weights against the float32 formulas
    to the limit the configuration's gradient is held to."""
    from penroz_tpu.ops.pallas import hc_mix
    d, T = (128, hc_mix.TOKEN_TILE) if path == "interpret" else (24, 16)
    assert hc_mix.fits(T, d, n, 4) == (path == "interpret")
    keys = jax.random.split(jax.random.key(9), 4)
    hc = _mixing(keys[0], n, d)
    body_w = 0.3 * jax.random.normal(keys[1], (d, d))
    shape = (2, T, d) if ends.get("expand") else (2, T, n, d)
    X = jax.random.normal(keys[2], shape)
    mod = _hc_module(n, d, **ends)
    plan = mod._cfg
    monkeypatch.setattr(M.HyperConnected, "_cfg", lambda self, X, ctx: plan(
        X, ctx)._replace(path=path))
    w = jax.random.normal(keys[3], mod.apply(
        X, M.Ctx(_hc_params(hc, body_w, d), platform="cpu")).shape)

    def loss(mix, compute, hc, body_w, X):
        ctx = M.Ctx(_hc_params(hc, body_w, d), platform="cpu",
                    compute_dtype=compute)
        X = X.astype(compute)
        if mix is _plain_mix and mod.expand:
            X = jnp.broadcast_to(X[:, :, None, :], (2, T, n, d))
        out = (mod.apply(X, ctx) if mix is None
               else mix(mod, X, ctx)).astype(jnp.float32)
        return (out * w).sum(), out

    grad = lambda mix, compute: jax.jit(jax.value_and_grad(
        lambda *a: loss(mix, compute, *a), (0, 1, 2), has_aux=True))(
            hc, body_w, X)
    ((_, got), got_grads) = grad(None, dtype)
    ((_, want), want_grads) = grad(_plain_mix, jnp.float32)
    if dtype == jnp.float32:
        _close(got, want, 1e-5)
        for g, r in zip(*map(jax.tree.leaves, (got_grads, want_grads))):
            _close(g, r, 1e-5 * max(1.0, float(jnp.abs(r).max())))
        return
    with open(CONFIG, encoding="utf-8") as f:
        limit = json.load(f)["correct"]["grad_rel_err"]
    flat = lambda tree: dict(enumerate(jax.tree.leaves(tree)))
    assert xing.tree_rel_error(flat(got), flat(want)) < limit
    for name, g, r in zip(("hc", "body", "X"), got_grads, want_grads):
        assert xing.tree_rel_error(flat(g), flat(r)) < limit, name


def test_hc_mix_plan_is_logged_once_and_spanned_per_trace(caplog,
                                                          monkeypatch):
    """Which path a compile's mixing took, beside ``hc plan:``: one INFO line
    a distinct plan and a ``penroz/hc_mix_plan`` span under whatever span of
    a job's trace is compiling (``GET /trace/{id}``): the kernel on one TPU
    where its tiles admit the shape, the fused passes anywhere else (off the
    TPU, under a mesh, at a toy width)."""
    import logging
    from jax.sharding import Mesh
    from penroz_tpu.ops import attention as attn_ops
    from penroz_tpu.utils import tracing
    meshed = attn_ops.Placement("tpu", Mesh(np.array(jax.devices()[:2]),
                                            ("data",)))
    toy, cell = [_hc_module(4, 24)], [_hc_module(4, 3584)]
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32)
    # the server's log_config.json, once a test of this worker has loaded
    # it, keeps the package's records from the root logger caplog hears
    monkeypatch.setattr(logging.getLogger("penroz_tpu"), "propagate", True)
    M._log_plan.cache_clear()
    tracing.reset()
    trace = tracing.maybe_trace("hc-mix-plan-job", job=True, route="/train/")
    with caplog.at_level(logging.INFO, logger=M.__name__), \
            tracing.use(trace), tracing.span("penroz/train_dispatch"):
        M.record_hc_plan(cell, tokens, True, "tpu", 2)
        M.record_hc_plan(cell, tokens, True, "tpu", 2)
        M.record_hc_plan(cell, tokens, True, "cpu", 2)
        M.record_hc_plan(cell, tokens, True, meshed, 2)
        M.record_hc_plan(toy, tokens, True, "tpu", 4)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("hc_mix plan:")]
    assert lines == [
        "hc_mix plan: path=kernel streams=4 features=3584 tokens=4096 "
        "bytes=528482304",
        "hc_mix plan: path=fused streams=4 features=3584 tokens=4096 "
        "bytes=528482304",
        "hc_mix plan: path=fused streams=4 features=24 tokens=4096 "
        "bytes=7077888"]
    dispatch = trace.to_dict()["root"]["children"][0]
    spans = [c["meta"] for c in dispatch["children"]
             if c["name"] == "penroz/hc_mix_plan"]
    assert [m["path"] for m in spans] == ["kernel", "kernel", "fused",
                                          "fused", "fused"]
    assert sum(c["name"] == "penroz/hc_plan"
               for c in dispatch["children"]) == 5
    trace.finish("completed")


# -- the router ---------------------------------------------------------------

def _router(bias, experts=16, k=4, **kw):
    mod = M.MixtureOfExperts(
        in_features=12, intermediate_size=8, num_experts=experts, top_k=k,
        dispatch="dropless", routed_scale=2.0, scoring="sigmoid",
        selection_bias=True,
        selection_bias_init=[float(b) for b in bias], **kw)
    mod.bind("moe")
    return mod


def test_sigmoid_router_bias_moves_the_choice_and_not_the_weight():
    experts, k = 16, 4
    keys = jax.random.split(jax.random.key(8), 3)
    router = 0.5 * jax.random.normal(keys[0], (12, experts))
    x = jax.random.normal(keys[1], (2, 9, 12))
    for planted in (None, 5):
        bias = np.asarray(xing.router_bias(experts, 1))
        if planted is not None:
            bias[planted] = 10.0
        mod = _router(bias)
        ctx = M.Ctx({"moe.router.weight": router.T}, mod.init_buffers())
        w, e = mod.route(x, ctx)
        want_w, want_e = xing.route(x, router, bias, top_k=k, scale=2.0,
                                    norm_topk=True)
        order = lambda w, e: jnp.take_along_axis(w, jnp.argsort(e, -1), -1)
        np.testing.assert_array_equal(np.sort(e, -1), np.sort(want_e, -1))
        _close(order(w, e), order(want_w, want_e))
        assert float(ctx.reported()["moe_bias_absmax"]) == pytest.approx(
            float(np.abs(bias).max()))
        # the weights are sigma-proportional: the scores of the chosen,
        # without the bias, renormalised, times 2
        s = jax.nn.sigmoid(x @ router)
        chosen = jnp.take_along_axis(s, e, -1)
        _close(w, 2.0 * chosen / chosen.sum(-1, keepdims=True))
        if planted is not None:
            assert bool((e == planted).any(-1).all())  # everyone chose it
            unbiased = xing.route(x, router, np.zeros(experts), top_k=k,
                                  scale=2.0, norm_topk=True)[1]
            assert not bool((unbiased == planted).any(-1).all())


def test_selection_bias_takes_no_gradient_and_follows_its_balance_rule():
    """Two optimizer steps of two micro-steps each: within a step the bias
    stands still (every micro-step routes by the same one), after it it has
    moved by rate · sign(mean load − load) of the step's tokens."""
    experts, k, d, rate = 8, 2, 12, 0.01
    layers = [{"embedding": {"num_embeddings": 32, "embedding_dim": d}},
              {"moe": {"in_features": d, "intermediate_size": 8,
                       "num_experts": experts, "top_k": k,
                       "dispatch": "dropless", "scoring": "sigmoid",
                       "selection_bias": True, "bias_update_rate": rate}},
              {"linear": {"in_features": d, "out_features": 32}},
              {"softmaxlast": {"dim": -1}}]
    mapper = Mapper(layers, {"sgd": {"lr": 0.0}})
    arch = CompiledArch.get(layers)
    params, buffers = mapper.init_params(arch.mods, seed=2)
    fn = arch.train_epoch_fn(mapper.optimizer, 2, with_ratios=False)
    opt_state = mapper.to_optimizer().init(params)
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.integers(0, 32, (2, 1, 16)))
    bias_key = "layers.1.selection_bias"
    mod = arch.mods[1]
    for _ in range(2):
        bias = buffers[bias_key]
        load = np.zeros(experts)
        for x in xs:                               # the step's micro-steps
            ctx = M.Ctx(params, buffers)
            _, e = mod.route(arch.mods[0].apply(x, ctx), ctx)
            load += np.bincount(np.asarray(e).ravel(), minlength=experts)
        out = fn(params, opt_state, buffers, xs, xs, jax.random.key(0))
        params, opt_state, buffers = out[:3]
        _close(buffers[bias_key],
               bias + rate * np.sign(load.mean() - load), 1e-7)
        assert float(jnp.abs(buffers["layers.1.selection_load"]).max()) == 0
        assert float(out[5]["moe_bias_absmax"]) == pytest.approx(
            float(jnp.abs(bias).max()))
    assert float(jnp.abs(buffers[bias_key]).max()) > 0


# -- the whole model ---------------------------------------------------------

def test_xing_preset_counts_the_configurations_parameters():
    """The configuration's count, without allocating it."""
    with open(CONFIG, encoding="utf-8") as f:
        cfg = json.load(f)
    layers = presets.xing_custom(**xing.preset_args(cfg))
    assert presets.param_count(layers) == cfg["parameters_held"]


def test_xing_first_optimizer_step_matches_the_reference_at_rehearse_sizes():
    """Loss and whole gradient of the first optimizer step (the epoch
    program, its gradient read back from AdamW's first moment as the
    benchmark's spy reads it) against the reference, and the counters the
    epoch returns."""
    import optax
    cfg = _rehearse_cfg()
    d = xing.dims(cfg)
    layers = presets.xing_custom(**xing.preset_args(cfg))
    mapper = Mapper(layers, cfg["optimizer"])
    arch = CompiledArch.get(layers)
    shapes, buffers = jax.eval_shape(
        lambda: mapper.init_params(arch.mods, seed=0))
    _, buffers = mapper.init_params(arch.mods, seed=0)
    params = xing.init_program_weights(cfg, 11)
    assert ({k: v.shape for k, v in params.items()}
            == {k: v.shape for k, v in shapes.items()})
    job = cfg["train"]
    steps = 2
    rng = np.random.default_rng(4)
    stream = rng.integers(0, d["vocab"], steps * job["block_size"] + 1)
    xs = jnp.asarray(stream[:-1].reshape(steps, 1, job["block_size"]))
    ys = jnp.asarray(stream[1:].reshape(steps, 1, job["block_size"]))
    fn = arch.train_epoch_fn(mapper.optimizer, steps, platform="cpu",
                             with_ratios=False)
    out = fn(dict(params), mapper.to_optimizer().init(params), buffers, xs,
             ys, jax.random.key(0))
    b1 = cfg["optimizer"]["adamw"]["betas"][0]
    got = {k: np.asarray(v) / (1 - b1) for k, v in
           optax.tree_utils.tree_get(out[1], "mu").items()}
    weights = xing.init_params(cfg, 11)
    loss, grads = xing.mean_loss_and_grad(
        weights, xs.reshape(steps, -1), ys.reshape(steps, -1),
        heads=d["heads"], rows=1)
    want = {k: np.asarray(v) for k, v in
            xing.as_gpt2_custom(grads, d["depth"]).items()}
    assert abs(float(out[3]) - loss) / loss < 1e-5
    assert xing.tree_rel_error(got, want) < cfg["correct"]["grad_rel_err"]
    worst = max(float(xing.sinkhorn_err(weights, x, heads=d["heads"]))
                for x in xs)
    assert float(out[5]["hc_sinkhorn_err"]) == pytest.approx(worst, rel=1e-3,
                                                             abs=1e-7)
    assert float(out[5]["moe_dropped"]) == 0
    assert float(out[5]["moe_bias_absmax"]) == pytest.approx(max(
        float(np.abs(xing.router_bias(d["experts"], i)).max())
        for i, kind in enumerate(d["mlp_types"]) if kind == "sparse"))


def test_xing_trains_through_the_model_and_generate_refuses(workdir,
                                                            toy_shards):
    """The normal path at a toy size: ``train_model`` (what ``PUT /train/``
    runs) trains the preset's DSL, its sampled progress rows carry both
    largest-value counters beside the routing ones, the selection bias has
    moved, and generation refuses with the one error (→ 400)."""
    from penroz_tpu.models.model import NeuralNetworkModel
    cfg = _rehearse_cfg(vocab_size=64)
    layers = presets.xing_custom(**xing.preset_args(cfg))
    model = NeuralNetworkModel("xing1", Mapper(layers, cfg["optimizer"]))
    bias = {k: np.asarray(v) for k, v in model.buffers.items()
            if k.endswith("selection_bias")}
    assert len(bias) == 4
    model.train_model("toy", shard=0, epochs=3, batch_size=2, block_size=16,
                      step_size=1)
    assert model.status["code"] == "Trained"
    row = model.progress[-1]
    assert 0.0 <= row["hc_sinkhorn_err"] < 0.5
    assert row["moe_bias_absmax"] > 0.0 and row["moe_dropped"] == 0
    assert any(not np.allclose(b, np.asarray(model.buffers[k]))
               for k, b in bias.items())
    with pytest.raises(ValueError, match="latent attention does not run"):
        model.generate_tokens([[1, 2]], block_size=16, max_new_tokens=2,
                              temperature=0.0)
