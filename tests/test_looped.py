"""A looped stack (``ops/modules.py::Looped``, ``presets.ouro_custom``): one
set of blocks run several times with shared weights, an exit after every
pass, the loss taken over the exit distribution.  The program against the
plain reference (``benchmark/reference/ouro.py``) on seeded weights, at a
small size, float32, on the CPU."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ouro
from penroz_tpu.models import presets
from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import (CompiledArch, NeuralNetworkModel,
                                     ServePipeline)
from penroz_tpu.ops import kv_cache as KV
from penroz_tpu.ops import losses
from penroz_tpu.ops import modules as M

CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 2,
       "vocab_size": 512, "max_position_embeddings": 64, "head_dim": 16,
       "intermediate_size": 96, "total_ut_steps": 4, "rope_theta": 1e6,
       "rms_norm_eps": 1e-6, "entropy_weight": 0.1}
ADAMW = {"adamw": {"lr": 3e-4, "betas": [0.9, 0.95], "eps": 1e-8,
                   "weight_decay": 0.1}}
SEED, HEADS, DEPTH, STEPS, T = 5, 4, 2, 4, 64


def _tokens(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], shape), jnp.int32)


@pytest.fixture(scope="module")
def arch():
    return CompiledArch.get(presets.ouro_custom(**ouro.preset_args(CFG)))


@pytest.fixture(scope="module")
def weights():
    """(the reference's weights, the same under the program's names), with
    the gate's bias and the norm gains moved off their initial values so
    that no term of a gradient is hidden by a 0 or a 1."""
    ref = ouro.init_params(CFG, SEED)
    rng = np.random.default_rng(1)
    params = jax.tree.map(
        lambda a: a + (0.1 * rng.standard_normal(a.shape)).astype(np.float32)
        if a.ndim <= 1 else a, ref.params)
    ref = ouro.Weights(params, ref.hyper)
    return ref, ouro.as_gpt2_custom(ref, DEPTH)


def _program_loss(arch, params, x, y, training=True):
    _, cost, ctx, _ = arch._forward(params, {}, x, y, training=training,
                                    skip_softmax=True)
    return cost, ctx.reported()


def test_loss_gradient_and_exits_match_the_reference(arch, weights):
    ref, params = weights
    x, y = _tokens((4, T)), _tokens((4, T), 1)
    want_loss, want_grad, (pass_loss, exit_mass) = ouro.mean_loss_and_grad(
        ref, x, y, heads=HEADS, rows=2, with_stats=True)
    want = ouro.as_gpt2_custom(want_grad, DEPTH)
    (loss, stats), grad = jax.value_and_grad(
        lambda p: _program_loss(arch, p, x, y), has_aux=True)(params)
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    assert set(grad) == set(want) == set(arch.param_order)
    for name in want:
        err = float(jnp.linalg.norm(grad[name] - want[name])
                    / jnp.linalg.norm(want[name]))
        assert err < 1e-4, (name, err)
    np.testing.assert_allclose(stats["pass_loss"], pass_loss, rtol=1e-5)
    np.testing.assert_allclose(stats["exit_mass"], exit_mass, rtol=1e-5)
    assert float(jnp.sum(stats["exit_mass"])) == pytest.approx(1.0, abs=1e-6)
    # evaluation (nothing recomputed) reads the same cost
    cost, _ = _program_loss(arch, params, x, y, training=False)
    assert float(cost) == pytest.approx(want_loss, rel=1e-5)


def test_last_pass_logits_are_the_models_output(arch, weights):
    ref, params = weights
    x = _tokens((2, T))
    want, _ = ouro.forward(ref, x, heads=HEADS)
    acts, _, _, _ = arch.forward(params, {}, x, skip_softmax=True)
    np.testing.assert_allclose(acts[-1], want[-1], rtol=2e-4, atol=2e-5)


def test_one_step_is_the_plain_sandwich_norm_stack(weights):
    """``steps=1`` with the same weights equals embedding, blocks, final
    norm and head as a flat list of layers."""
    _, params = weights
    looped = presets.ouro_custom(**{**ouro.preset_args(CFG), "steps": 1})
    inner = looped[1]["looped"]
    flat = ([looped[0]] + inner["body"]
            + [inner["exit"]["norm"], inner["exit"]["head"], looped[2]])
    rename = {"layers.0.weight": "layers.0.weight",
              "layers.1.norm.weight": f"layers.{1 + DEPTH}.weight",
              "layers.1.head.weight": f"layers.{2 + DEPTH}.weight"}
    flat_params = {}
    for name, value in params.items():
        if name.startswith("layers.1.body."):
            i, rest = name[len("layers.1.body."):].split(".", 1)
            flat_params[f"layers.{1 + int(i)}.{rest}"] = value
        elif name in rename:
            flat_params[rename[name]] = value
    x, y = _tokens((2, T)), _tokens((2, T), 1)
    one, plain = CompiledArch.get(looped), CompiledArch.get(flat)
    assert set(flat_params) == set(plain.param_order)
    got, _, _, _ = one.forward(params, {}, x, skip_softmax=True)
    want, cost, _, _ = plain.forward(flat_params, {}, x, y,
                                     skip_softmax=True)
    np.testing.assert_allclose(got[-1], want[-1], rtol=1e-5, atol=1e-6)
    # with one exit the exit loss is that exit's cross-entropy: p = (1,)
    loss, stats = _program_loss(one, params, x, y)
    assert float(loss) == pytest.approx(float(cost), rel=1e-6)
    np.testing.assert_allclose(stats["exit_mass"], [1.0])


def test_shared_weight_gradient_is_the_sum_over_unrolled_copies(arch,
                                                                weights):
    """Four copies of the parameters, one a pass, unrolled by hand: each
    shared weight's gradient is the sum of its four copies'."""
    _, params = weights
    x, y = _tokens((2, T)), _tokens((2, T), 1)
    embed, loop = arch.mods[0], arch.looped

    def unrolled(copies):
        ctxs = [M.Ctx(c) for c in copies]
        u, rows, gates = embed.apply(x, ctxs[0]), [], []
        for ctx in ctxs:
            for block in loop.body:
                u = block.apply(u, ctx)
            u = loop.norm.apply(u, ctx)
            ce, gate = loop._exit(ctx, u, y)
            rows.append(ce)
            gates.append(gate)
        return losses.expected_exit_loss(jnp.stack(rows), jnp.stack(gates),
                                         loop.entropy_weight)[0]

    copies = jax.grad(unrolled)([dict(params) for _ in range(STEPS)])
    shared = jax.grad(lambda p: _program_loss(arch, p, x, y)[0])(params)
    for name in params:
        if name == "layers.0.weight":
            continue        # the embedding is applied once: copy 0 alone
        parts = [c[name] for c in copies]
        assert sum(float(jnp.linalg.norm(p)) > 0 for p in parts) >= (
            STEPS - 1 if name.startswith("layers.1.gate") else STEPS), name
        np.testing.assert_allclose(shared[name], sum(parts), rtol=2e-4,
                                   atol=1e-7, err_msg=name)


def test_exit_distribution_sums_to_one_and_beta_weighs_the_entropy():
    rng = np.random.default_rng(3)
    gates = jnp.asarray(3.0 * rng.standard_normal((STEPS, 5, 7)), jnp.float32)
    ce = jnp.asarray(rng.uniform(1.0, 9.0, (STEPS, 5, 7)), jnp.float32)
    p = losses.exit_distribution(gates)
    np.testing.assert_allclose(jnp.sum(p, 0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p, ouro.exit_distribution(gates), rtol=1e-6)
    lam = jax.nn.sigmoid(gates)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(p[-1], jnp.prod(1 - lam[:-1], 0), rtol=1e-5)
    entropy = -jnp.sum(p * jnp.log(p), 0)
    plain, stats = losses.expected_exit_loss(ce, gates, 0.0)
    assert float(plain) == pytest.approx(float(jnp.mean(jnp.sum(p * ce, 0))),
                                         rel=1e-6)
    for beta in (0.1, 0.7):
        loss, _ = losses.expected_exit_loss(ce, gates, beta)
        assert float(loss - plain) == pytest.approx(
            -beta * float(jnp.mean(entropy)), rel=1e-5)
    np.testing.assert_allclose(stats["pass_loss"], jnp.mean(ce, (1, 2)),
                               rtol=1e-6)
    np.testing.assert_allclose(stats["exit_mass"], jnp.mean(p, (1, 2)),
                               rtol=1e-6)


@pytest.mark.parametrize("state", ["dense", "int8"])
def test_prefill_then_decode_equals_the_full_forward(arch, weights, state,
                                                     monkeypatch):
    """Through ``steps × layers`` cache slots: the logits of every position,
    prefilled or decoded one token at a time, are the full forward's
    pass-4 logits (logits, not tokens)."""
    ref, params = weights
    monkeypatch.setenv(KV.TURBO_QUANT_ENV, "1" if state == "int8" else "0")
    x = _tokens((1, 24))
    want = np.asarray(ouro.forward(ref, x, heads=HEADS)[0][-1])
    specs = arch.kv_specs
    assert len(specs) == STEPS * DEPTH == arch.looped.plan(False)[
        "cache_slots"]
    kv = KV.create_kv_state(specs, 1, 32, jnp.float32)
    prefill = 16
    acts, _, _, kv = arch.forward(params, {}, x[:, :prefill], kv=kv,
                                  skip_softmax=True)
    got = [np.asarray(acts[-1])]
    for t in range(prefill, x.shape[1]):
        acts, _, _, kv = arch.forward(params, {}, x[:, t:t + 1], kv=kv,
                                      skip_softmax=True)
        got.append(np.asarray(acts[-1]))
    tol = dict(rtol=2e-4, atol=2e-5) if state == "dense" else dict(
        rtol=0.1, atol=0.02)
    np.testing.assert_allclose(np.concatenate(got, axis=1), want, **tol)
    # every (pass, layer) wrote its own slot
    filled = [float(jnp.abs(k.astype(jnp.float32)).sum()) for k in kv.k]
    assert len(filled) == STEPS * DEPTH and all(f > 0 for f in filled)
    assert len({round(f, 3) for f in filled}) == len(filled)


def _model(weights, model_id="loop"):
    model = NeuralNetworkModel(model_id, Mapper(
        presets.ouro_custom(**ouro.preset_args(CFG)), ADAMW))
    model.params = dict(weights[1])
    return model


def test_generate_plain_path_and_decode_engine_agree(weights, workdir,
                                                     monkeypatch):
    """``/generate/``'s plain path and the continuous-batching engine with
    the dense cache emit the reference's own greedy continuation."""
    import queue
    from penroz_tpu.serve import decode_scheduler
    monkeypatch.setenv(KV.PAGED_ENV, "0")
    ref, _ = weights
    model = _model(weights)
    model.serialize(sync_flush=True)
    prompt, new = [int(t) for t in _tokens((9,), 4)], 8
    seq = list(prompt)
    for _ in range(new):
        z, _ = ouro.forward(ref, jnp.asarray([seq]), heads=HEADS)
        seq.append(int(jnp.argmax(z[-1, 0, -1])))
    plain = model.generate_tokens([prompt], T, new, temperature=0.0)
    assert [int(t) for t in plain] == seq
    events = queue.Queue()
    engine = decode_scheduler.DecodeEngine("loop", T, 0.0, None, capacity=2)
    try:
        engine.submit(decode_scheduler.Request(
            prompt, new, None, lambda kind, value: events.put((kind, value))))
        got = []
        while True:
            kind, value = events.get(timeout=180)
            if kind == "done":
                break
            assert kind == "token", value
            got.append(int(value))
    finally:
        engine.shutdown()
        decode_scheduler.reset()
    assert got == seq[len(prompt):]


def test_what_a_looped_model_does_not_run_refuses_with_one_error(
        arch, monkeypatch):
    monkeypatch.setenv(KV.PAGED_ENV, "1")
    with pytest.raises(ValueError, match="a looped model does not run with "
                                         "the paged KV pool"):
        arch.kv_specs
    with pytest.raises(ValueError, match="a looped model does not run with "
                                         "serving pipeline stages"):
        ServePipeline(arch, 2)
    with pytest.raises(ValueError, match="ssm layers inside a looped"):
        layers = presets.ouro_custom(**ouro.preset_args(CFG))
        layers[1]["looped"]["body"].append(
            {"ssm": {"num_heads": 2, "head_dim": 8, "value_dim": 8}})
        CompiledArch.get(layers)


def test_dsl_round_trip_and_hf_config(arch):
    layers = presets.ouro_custom(**ouro.preset_args(CFG))
    again = json.loads(json.dumps(layers))
    assert again == layers
    assert CompiledArch.get(again).param_order == arch.param_order
    loop = arch.looped
    assert (loop.steps, len(loop.body), loop.slots_per_pass) == (STEPS,
                                                                 DEPTH, DEPTH)
    assert loop.plan(False) == {"steps": 4, "layers": 2, "applications": 8,
                                "recomputed_applications": 0,
                                "cache_slots": 8, "kept_outputs": ""}
    with pytest.raises(ValueError, match="looped takes steps, body"):
        bad = json.loads(json.dumps(layers))
        del bad[1]["looped"]["exit"]["gate"]
        CompiledArch.get(bad)

    class HF:
        model_type = "ouro"
        hidden_size, num_attention_heads, num_key_value_heads = 64, 4, 4
        head_dim, intermediate_size, num_hidden_layers = 16, 96, 2
        vocab_size, total_ut_steps, rope_theta = 512, 4, 1000000
        rms_norm_eps, hidden_act, rope_scaling = 1e-6, "silu", None
        use_sliding_window, sliding_window = False, None
        early_exit_threshold = 1.0

    assert Mapper.from_hf_config(HF()) == layers
    assert Mapper.from_hf_config(HF(), n_layer_override=1) == \
        presets.ouro_custom(**{**ouro.preset_args(CFG), "depth": 1})
    HF.num_key_value_heads = 2
    with pytest.raises(ValueError, match="grouped K/V"):
        Mapper.from_hf_config(HF())


def test_epoch_program_returns_the_exits_after_its_five_results(arch,
                                                                weights):
    """The spy of the benchmark reads results 1 (AdamW's state) and 3 (the
    cost): the exits come sixth; a plain model still returns five; the
    micro-stepped epoch agrees with the fused one."""
    _, params = weights
    fresh = lambda: jax.tree.map(jnp.copy, params)  # the programs donate
    state = lambda: Mapper([], ADAMW).to_optimizer().init(params)
    xs, ys = _tokens((2, 2, T)), _tokens((2, 2, T), 1)
    key = jax.random.key(0)
    fused = arch.train_epoch_fn(ADAMW, 2)
    out = fused(fresh(), state(), {}, xs, ys, key)
    assert len(out) == 6 and set(out[5]) == {"pass_loss", "exit_mass"}
    costs = [float(_program_loss(arch, params, xs[i], ys[i])[0])
             for i in range(2)]
    assert float(out[3]) == pytest.approx(np.mean(costs), rel=1e-5)
    assert out[5]["pass_loss"].shape == out[5]["exit_mass"].shape == (STEPS,)
    micro, finalize = arch.train_micro_fns(ADAMW, 2)
    grads = jax.tree.map(jnp.zeros_like, params)
    bufs, cost = {}, arch.zero_cost_sum()
    for i in range(2):
        bufs, grads, cost = micro(params, bufs, grads, cost, xs[i], ys[i],
                                  key, i)
    stepped = finalize(fresh(), state(), grads, bufs, cost)
    assert float(stepped[3]) == pytest.approx(float(out[3]), rel=1e-5)
    np.testing.assert_allclose(stepped[5]["exit_mass"], out[5]["exit_mass"],
                               rtol=1e-5)
    plain = CompiledArch.get(presets.makemore_mlp())
    assert plain.looped is None and set(plain.zero_cost_sum()) == {"cost"}


def _checkpoint_as_the_parent_did(monkeypatch, recompute=True):
    """``Looped`` as it was before it kept anything: every application under
    a bare ``jax.checkpoint`` (or, ``recompute=False``, under none), rebuilt
    here by wrapping what the container calls."""
    real = jax.checkpoint
    monkeypatch.setattr(
        jax, "checkpoint",
        lambda fn, policy=None: real(fn) if recompute else fn)


@pytest.mark.parametrize("jitted", [False, True])
def test_keeping_the_exits_logsumexp_changes_no_bit(arch, weights,
                                                    monkeypatch, jitted):
    """On the CPU attention takes the jnp path, which names nothing, so of
    the three names only the cross-entropy's logsumexp is kept here (the
    flash forward's two: ``test_keeping_the_flash_forwards_results_...``).
    The policy keeps what the recomputation would have made again, the same
    arithmetic: loss and gradient equal, bit for bit, those of a bare
    ``jax.checkpoint``, and to tolerance those with nothing recomputed."""
    _, params = weights
    x, y = _tokens((2, T)), _tokens((2, T), 1)

    def loss_and_grad():
        fn = jax.value_and_grad(lambda p: _program_loss(arch, p, x, y)[0])
        return (jax.jit(fn) if jitted else fn)(params)

    loss, grad = loss_and_grad()
    with monkeypatch.context() as patch:
        _checkpoint_as_the_parent_did(patch)
        bare_loss, bare_grad = loss_and_grad()
    with monkeypatch.context() as patch:
        _checkpoint_as_the_parent_did(patch, recompute=False)
        plain_loss, plain_grad = loss_and_grad()
    assert float(loss) == float(bare_loss)
    assert float(loss) == pytest.approx(float(plain_loss), rel=1e-6)
    for name in params:
        np.testing.assert_array_equal(grad[name], bare_grad[name],
                                      err_msg=name)
        np.testing.assert_allclose(grad[name], plain_grad[name], rtol=1e-4,
                                   atol=1e-7, err_msg=name)


def test_in_bfloat16_no_bit_changes_where_xla_rounds_as_written(arch, weights,
                                                               monkeypatch):
    """bfloat16 compute, compiled with ``xla_allow_excess_precision`` off:
    the gradient with the policy is the bare checkpoint's bit for bit.
    (With XLA's default it need not be: two programs of another structure
    keep excess precision in other places.  On the chip not even without
    it: the kept ``o`` comes from the forward's compilation of the matmuls
    before it, and a second compilation of one matmul need not round
    alike; the looped cell's ``grad_rel_err`` moved in its fourth digit,
    either way by seed, PR 40.)"""
    _, params = weights
    x, y = _tokens((2, T)), _tokens((2, T), 1)

    def loss(p):
        _, cost, _, _ = arch.forward(p, {}, x, y, training=True,
                                     skip_softmax=True,
                                     compute_dtype=jnp.bfloat16)
        return cost

    def compiled_grad():
        return jax.jit(jax.value_and_grad(loss)).lower(params).compile(
            compiler_options={"xla_allow_excess_precision": False})(params)

    loss_kept, grad = compiled_grad()
    _checkpoint_as_the_parent_did(monkeypatch)
    loss_bare, bare_grad = compiled_grad()
    assert float(loss_kept) == float(loss_bare)
    for name in params:
        np.testing.assert_array_equal(grad[name], bare_grad[name],
                                      err_msg=name)


@pytest.mark.parametrize("training", [True, False])
def test_plan_names_what_the_recomputation_keeps(arch, training):
    """``kept_outputs``: the three names of the policy where the loop
    recomputes, none where it does not."""
    names = "penroz_flash_out,penroz_flash_lse,penroz_ce_lse"
    assert arch.looped.plan(training) == {
        "steps": STEPS, "layers": DEPTH, "applications": STEPS * DEPTH,
        "recomputed_applications": STEPS * DEPTH if training else 0,
        "cache_slots": STEPS * DEPTH,
        "kept_outputs": names if training else ""}
    assert names.split(",") == list(M._kept_names())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_keeping_the_flash_forwards_results_changes_no_bit(dtype):
    """The flash kernels (interpret mode, the CPU) between two matmuls under
    ``jax.checkpoint``: with the loop's policy the backward finds ``o`` and
    the logsumexp kept and its jaxpr holds the forward kernel once, with a
    bare checkpoint twice, and the gradients are equal bit for bit."""
    from penroz_tpu.ops.pallas import flash_attention as fa
    heads, dim, rows = 2, 64, 128
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, rows, heads * dim)), dtype)
    w_in = jnp.asarray(0.1 * rng.standard_normal((heads * dim,
                                                  3 * heads * dim)), dtype)
    w_out = jnp.asarray(0.1 * rng.standard_normal((heads * dim, heads * dim)),
                        dtype)

    def block(x, w_in, w_out):
        return fa.flash_attention_btd(x @ w_in, heads=heads,
                                      interpret=True) @ w_out

    def grad(policy):
        return jax.grad(lambda *a: jnp.square(jax.checkpoint(
            block, policy=policy)(*a).astype(jnp.float32)).sum(),
            argnums=(0, 1, 2))

    keep = jax.checkpoint_policies.save_only_these_names(*M._kept_names())
    forwards = lambda policy: _kernel_calls(jax.make_jaxpr(grad(policy))(
        x, w_in, w_out).jaxpr)["penroz_flash_fwd"]
    assert (forwards(None), forwards(keep)) == (2, 1)
    for kept, bare in zip(jax.jit(grad(keep))(x, w_in, w_out),
                          jax.jit(grad(None))(x, w_in, w_out)):
        np.testing.assert_array_equal(kept, bare)


def test_a_looped_body_whose_attention_gives_no_head_dim_trains():
    """``head_dim`` is optional in the DSL: after a clamp of the projection
    (OLMo's ``clip_qkv``) the model builder cannot infer it either, and
    the attention derives it from the projection's width when applied.
    The loop's plan reckons from no attribute of its layers, so such a
    body trains."""
    layers = json.loads(json.dumps(
        presets.ouro_custom(**ouro.preset_args(CFG))))
    for block in layers[1]["looped"]["body"]:
        items = block["transformerblock"]["attn_block"]["sequential"]
        del items[2]["attention"]["head_dim"]
        items.insert(2, {"clamp": {"min": -1e4, "max": 1e4}})
    bare = CompiledArch.get(layers)
    assert all(m.head_dim is None for block in bare.looped.body
               for m in block.walk()
               if isinstance(m, M.CausalSelfAttention))
    params, _ = Mapper(layers, ADAMW).init_params(bare.mods, seed=SEED)
    x, y = _tokens((2, T)), _tokens((2, T), 1)
    loss, grad = jax.value_and_grad(
        lambda p: _program_loss(bare, p, x, y)[0])(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all() for g in grad.values())
    assert any(float(jnp.abs(g).max()) > 0 for g in grad.values())


def _kernel_calls(jaxpr, found=None) -> dict:
    """How often each named Pallas call stands in ``jaxpr``, nested ones
    (a ``jax.checkpoint``'s, a ``custom_vjp``'s) included."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[name] = found.get(name, 0) + 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(inner, found)
    return found


@pytest.mark.parametrize("parent", [False, True])
def test_recomputation_runs_neither_kernels_forward_again(monkeypatch,
                                                          parent):
    """The gradient of a looped stack traced with the kernels in it (the
    TPU's path, bf16; nothing is lowered): the flash forward stands once an
    application and the cross-entropy forward once an exit, where the
    parent's bare ``jax.checkpoint`` had each twice."""
    from penroz_tpu.models import dsl
    steps, depth = 3, 2
    small = CompiledArch.get(presets.ouro_custom(
        d=128, heads=2, head_dim=64, intermediate=256, depth=depth,
        steps=steps, vocab=1024))
    shapes, _ = jax.eval_shape(
        lambda: dsl.init_module_params(small.mods, seed=0))
    params = {k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16)
              for k, v in shapes.items()}
    x = jax.ShapeDtypeStruct((2, 128), jnp.int32)

    def loss(p, x, y):
        _, cost, _, _ = small.forward(p, {}, x, y, training=True,
                                      skip_softmax=True,
                                      compute_dtype=jnp.bfloat16,
                                      platform="tpu")
        return cost

    if parent:
        _checkpoint_as_the_parent_did(monkeypatch)
    calls = _kernel_calls(jax.make_jaxpr(jax.grad(loss))(params, x, x).jaxpr)
    again = 2 if parent else 1
    assert calls == {"penroz_flash_fwd": again * steps * depth,
                     "penroz_flash_bwd_delta": steps * depth,
                     "penroz_flash_bwd": steps * depth,
                     "penroz_ce_fwd": again * steps,
                     "penroz_ce_bwd": steps}
