"""Mixture-of-experts tests: dense top-k routing vs a per-expert loop
oracle, expert-parallel sharding on the virtual mesh, and end-to-end
training through the DSL."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import CompiledArch, NeuralNetworkModel
from penroz_tpu.ops import modules as M
from penroz_tpu.parallel import mesh as mesh_lib, sharding

# CI tier: heavier compiles (see pyproject markers / ci.yml shards).
pytestmark = pytest.mark.runtime

SGD = {"sgd": {"lr": 0.1}}


def _moe(d=8, h=16, e=4, k=2):
    mod = M.MixtureOfExperts(in_features=d, intermediate_size=h,
                             num_experts=e, top_k=k)
    mod.bind("moe")
    params = mod.init(jax.random.key(0))
    return mod, params


def _oracle(mod, params, x):
    """Per-expert python loop: route, run each selected expert, combine."""
    router = np.asarray(params[mod.key("router.weight")])
    wg = np.asarray(params[mod.key("experts.gate_proj.weight")])
    wu = np.asarray(params[mod.key("experts.up_proj.weight")])
    wd = np.asarray(params[mod.key("experts.down_proj.weight")])
    xb = np.asarray(x)
    B, T, D = xb.shape
    logits = xb @ router.T
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(xb)
    for b in range(B):
        for t in range(T):
            idx = np.argsort(-probs[b, t])[:mod.top_k]
            w = probs[b, t, idx]
            w = w / w.sum()
            for j, eidx in enumerate(idx):
                gate = xb[b, t] @ wg[eidx].T
                up = xb[b, t] @ wu[eidx].T
                hidden = (gate / (1 + np.exp(-gate))) * up  # silu
                out[b, t] += w[j] * (hidden @ wd[eidx].T)
    return out


def test_moe_matches_per_expert_oracle():
    mod, params = _moe()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 3, 8)),
                    jnp.float32)
    got = mod.apply(x, M.Ctx(params))
    np.testing.assert_allclose(np.asarray(got), _oracle(mod, params, x),
                               atol=1e-5)


def test_moe_top1_selects_single_expert():
    """With top_k=1 the output equals exactly the argmax expert's MLP."""
    mod, params = _moe(e=3, k=1)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 2, 8)),
                    jnp.float32)
    got = np.asarray(mod.apply(x, M.Ctx(params)))
    np.testing.assert_allclose(got, _oracle(mod, params, x), atol=1e-5)


def test_moe_router_weights_sum_to_one():
    mod, params = _moe()
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 4, 8)),
                    jnp.float32)
    w = np.asarray(mod.router_weights(x, M.Ctx(params)))
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    # exactly top_k nonzero entries per token
    assert ((w > 0).sum(-1) == mod.top_k).all()


def test_moe_param_shapes_and_validation():
    mod, params = _moe(d=8, h=16, e=4)
    assert params[mod.key("experts.gate_proj.weight")].shape == (4, 16, 8)
    assert params[mod.key("experts.down_proj.weight")].shape == (4, 8, 16)
    assert params[mod.key("router.weight")].shape == (4, 8)
    with pytest.raises(ValueError, match="top_k"):
        M.MixtureOfExperts(8, 16, 4, top_k=5)


def test_moe_expert_parallel_matches_replicated(cpu_devices):
    """Forward with expert-sharded stacked weights == replicated forward."""
    mesh = mesh_lib.make_mesh(cpu_devices[:4], expert=4)
    mod, params = _moe(d=8, h=16, e=4, k=2)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 4, 8)),
                    jnp.float32)
    expected = np.asarray(mod.apply(x, M.Ctx(params)))

    specs = {k: sharding.param_spec(k, tuple(v.shape), mesh)
             for k, v in params.items()}
    from jax.sharding import PartitionSpec as P
    assert specs[mod.key("experts.gate_proj.weight")] == \
        P("expert", None, None)
    sharded = sharding.shard_params(params, mesh)
    out = jax.jit(lambda p, xb: mod.apply(xb, M.Ctx(p)))(sharded, x)
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)


def test_moe_dsl_train_and_generate(workdir, toy_shards):
    """An MoE transformer block trains and generates through the DSL."""
    d, vocab, block = 16, 64, 16
    layers = ([{"summation": [
                 {"embedding": {"num_embeddings": vocab, "embedding_dim": d}},
                 {"position": {"num_embeddings": block,
                               "embedding_dim": d}}]}]
              + [{"residual": [
                  {"sequential": [
                      {"layernorm": {"normalized_shape": d}},
                      {"linear": {"in_features": d, "out_features": 3 * d}},
                      {"attention": {"num_heads": 2, "dropout": 0.0}},
                      {"linear": {"in_features": d, "out_features": d}}]},
                  {"sequential": [
                      {"layernorm": {"normalized_shape": d}},
                      {"moe": {"in_features": d, "intermediate_size": 2 * d,
                               "num_experts": 4, "top_k": 2}}]}]}]
              + [{"layernorm": {"normalized_shape": d}},
                 {"linear": {"in_features": d, "out_features": vocab,
                             "bias": False}},
                 {"softmaxlast": {"dim": -1}}])
    model = NeuralNetworkModel("moe1", Mapper(layers, SGD))
    before = {k: np.asarray(v) for k, v in model.params.items()}
    model.train_model("toy", shard=0, epochs=2, batch_size=2, block_size=16,
                      step_size=2)
    assert model.status["code"] == "Trained"
    moe_key = next(k for k in model.params if "experts.gate_proj" in k)
    assert not np.allclose(before[moe_key], np.asarray(model.params[moe_key]))
    tokens = model.generate_tokens([[1, 2]], block_size=16, max_new_tokens=4,
                                   temperature=0.0)
    assert len(tokens) == 6


def test_moe_aux_loss_and_router_stats():
    """Load-balance aux loss accumulates into ctx during training and the
    per-expert routing fractions land in buffer_updates (observable expert
    collapse — the dense dispatch otherwise hides it)."""
    mod = M.MixtureOfExperts(8, 16, num_experts=4, top_k=2,
                             aux_loss_coef=0.01)
    mod.bind("moe")
    params = mod.init(jax.random.key(0))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 6, 8)),
                    jnp.float32)

    ctx = M.Ctx(params, mod.init_buffers(), training=True,
                rng=jax.random.key(1))
    mod.apply(x, ctx)
    assert len(ctx.aux_losses) == 1
    aux = float(ctx.aux_losses[0])
    # Switch aux = coef · E · Σ f·P ≥ coef for any routing; ≈ coef at uniform
    assert aux >= 0.01 - 1e-6
    frac = np.asarray(ctx.buffer_updates[mod.key("router_fraction")])
    assert frac.shape == (4,)
    np.testing.assert_allclose(frac.sum(), 1.0, atol=1e-5)

    # Inference and coef=0 add no aux loss.
    ctx_eval = M.Ctx(params, mod.init_buffers(), training=False)
    mod.apply(x, ctx_eval)
    assert ctx_eval.aux_losses == []
    mod0 = M.MixtureOfExperts(8, 16, num_experts=4, top_k=2)
    mod0.bind("moe")
    ctx0 = M.Ctx(mod0.init(jax.random.key(0)), mod0.init_buffers(),
                 training=True, rng=jax.random.key(1))
    mod0.apply(x, ctx0)
    assert ctx0.aux_losses == []


def test_moe_aux_loss_reaches_training_cost():
    """The aux term backpropagates: router grads are nonzero even when the
    task loss is flat in the router (symmetric experts)."""
    layers = [{"linear": {"in_features": 4, "out_features": 8}},
              {"moe": {"in_features": 8, "intermediate_size": 8,
                       "num_experts": 2, "top_k": 1,
                       "aux_loss_coef": 0.1}},
              {"linear": {"in_features": 8, "out_features": 4}}]
    mapper = Mapper(layers, {"sgd": {"lr": 0.1}})
    arch = CompiledArch.get(mapper.layers)
    params, buffers = mapper.init_params(arch.mods, seed=0)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 5, 4)),
                    jnp.float32)
    y = jnp.asarray(np.random.default_rng(3).normal(size=(2, 5, 4)),
                    jnp.float32)

    def loss(p):
        _, cost, _, _ = arch.forward(p, buffers, x, y, training=True,
                                     rng=jax.random.key(0))
        return cost

    def loss_no_aux(p):
        _, cost, _, _ = arch.forward(p, buffers, x, y, training=False)
        return cost

    with_aux = float(loss(params))
    without = float(loss_no_aux(params))
    assert with_aux > without  # aux term present in the training cost


def test_moe_train_epoch_and_checkpoint_migration(workdir):
    """MoE trains through train_epoch_fn (buffer updates must not change
    the lax.scan carry structure), and checkpoints saved before the
    router_fraction buffer existed still train after deserialize."""
    from penroz_tpu.utils import checkpoint
    layers = [{"linear": {"in_features": 4, "out_features": 8}},
              {"moe": {"in_features": 8, "intermediate_size": 8,
                       "num_experts": 2, "top_k": 1}},
              {"linear": {"in_features": 8, "out_features": 4}}]
    model = NeuralNetworkModel("moemig", Mapper(layers, {"sgd": {"lr": 0.1}}))
    model.serialize(sync_flush=True)

    # Simulate a pre-router_fraction checkpoint: strip the buffer key.
    blob = checkpoint.load("moemig")
    blob["buffers"] = {k: v for k, v in blob["buffers"].items()
                       if "router_fraction" not in k}
    checkpoint.save("moemig", blob, sync_flush=True)

    restored = NeuralNetworkModel.deserialize("moemig")
    assert any("router_fraction" in k for k in restored.buffers)  # migrated

    epoch_fn = restored.arch.train_epoch_fn(restored.optimizer_config,
                                            num_steps=2)
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(2, 2, 5, 4)), jnp.float32)
    ys = jnp.asarray(rng.normal(size=(2, 2, 5, 4)), jnp.float32)
    params, opt_state, buffers, cost, _ = epoch_fn(
        restored.params, restored.opt_state, restored.buffers, xs, ys,
        jax.random.key(0))
    assert np.isfinite(float(cost))
    frac = np.asarray(
        next(v for k, v in buffers.items() if "router_fraction" in k))
    np.testing.assert_allclose(frac.sum(), 1.0, atol=1e-5)


def _moe_cap(d=8, h=16, e=4, k=2, cf=8.0):
    mod = M.MixtureOfExperts(in_features=d, intermediate_size=h,
                             num_experts=e, top_k=k, dispatch="capacity",
                             capacity_factor=cf)
    mod.bind("moe")
    # identical params to the dense module (same init key)
    params = mod.init(jax.random.key(0))
    return mod, params


def test_moe_capacity_matches_dense_when_roomy():
    """With capacity >= tokens no token drops, so the packed dispatch is
    numerically the dense dispatch."""
    dense, params = _moe()
    cap, _ = _moe_cap(cf=8.0)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 5, 8)),
                    jnp.float32)
    np.testing.assert_allclose(np.asarray(cap.apply(x, M.Ctx(params))),
                               np.asarray(dense.apply(x, M.Ctx(params))),
                               atol=1e-5)


def test_moe_capacity_drops_overflow_tokens():
    """A starving capacity factor loses expert contributions (Switch
    semantics): outputs differ from dense, and forcing every token onto
    one expert caps the number served."""
    dense, params = _moe(k=1)
    cap, _ = _moe_cap(k=1, cf=0.25)  # C = ceil(1*10/4*0.25) = 1 slot/expert
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 10, 8)),
                    jnp.float32)
    out_cap = np.asarray(cap.apply(x, M.Ctx(params)))
    out_dense = np.asarray(dense.apply(x, M.Ctx(params)))
    assert not np.allclose(out_cap, out_dense, atol=1e-5)
    # dropped tokens produce exactly zero rows (top-1: sole contribution
    # lost); served tokens match dense exactly
    zero_rows = np.all(np.abs(out_cap) < 1e-7, axis=-1)[0]
    assert zero_rows.sum() >= 10 - 4  # ≥ tokens - E·C rows dropped
    served = ~zero_rows
    np.testing.assert_allclose(out_cap[0][served], out_dense[0][served],
                               atol=1e-5)


def test_moe_capacity_gradients_flow():
    mod, params = _moe_cap(cf=8.0)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 4, 8)),
                    jnp.float32)

    def loss(p):
        return jnp.sum(mod.apply(x, M.Ctx(p)) ** 2)

    grads = jax.grad(loss)(params)
    total = sum(float(jnp.sum(jnp.abs(g))) for g in grads.values())
    assert np.isfinite(total) and total > 0


def test_moe_capacity_expert_parallel_matches_replicated(cpu_devices):
    """Capacity dispatch under the expert axis == single-device result."""
    mod, params = _moe_cap(e=4, cf=8.0)
    mesh = mesh_lib.make_mesh(cpu_devices[:4], expert=4)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 4, 8)),
                    jnp.float32)
    expected = mod.apply(x, M.Ctx(params))
    sharded = {k: jax.device_put(v, jax.sharding.NamedSharding(
        mesh, sharding.param_spec(k, tuple(v.shape), mesh)))
        for k, v in params.items()}
    got = jax.jit(lambda p, xx: mod.apply(xx, M.Ctx(p)))(sharded, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=1e-5)


def test_moe_capacity_dsl_validation():
    with pytest.raises(ValueError, match="dispatch"):
        M.MixtureOfExperts(8, 16, 4, dispatch="alltoall")
    with pytest.raises(ValueError, match="capacity_factor"):
        M.MixtureOfExperts(8, 16, 4, dispatch="capacity",
                           capacity_factor=0.0)


def test_moe_capacity_pads_awkward_token_counts():
    """Non-divisible (incl. prime) B*T pads with masked rows instead of
    shrinking the dispatch group; numerics still match dense."""
    dense, params = _moe()
    cap, _ = _moe_cap(cf=8.0)
    for T in (7, 521):  # sub-group prime; prime above DISPATCH_GROUP (pads)
        x = jnp.asarray(np.random.default_rng(5).normal(size=(1, T, 8)),
                        jnp.float32)
        np.testing.assert_allclose(np.asarray(cap.apply(x, M.Ctx(params))),
                                   np.asarray(dense.apply(x, M.Ctx(params))),
                                   atol=1e-5)


def _capacity_moe(d=8, h=16, e=4, k=2, **kw):
    mod = M.MixtureOfExperts(in_features=d, intermediate_size=h,
                             num_experts=e, top_k=k, dispatch="capacity",
                             **kw)
    mod.bind("moe")
    return mod, mod.init(jax.random.key(0))


def test_moe_capacity_ep_alltoall_matches_single_device(cpu_devices):
    """all_to_all token routing (ep_mesh set) == the single-device packed
    dispatch: same grouping/slot math via the shared _dispatch_plan, so
    routing AND drops are identical — only the comm schedule differs."""
    mesh = mesh_lib.make_mesh(cpu_devices[:4], expert=4)
    mod, params = _capacity_moe()
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 6, 8)),
                    jnp.float32)
    expected = np.asarray(mod.apply(x, M.Ctx(params)))
    sharded = sharding.shard_params(params, mesh)
    out = jax.jit(lambda p, xb: mod.apply(xb, M.Ctx(p, ep_mesh=mesh)))(
        sharded, x)
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)


def test_moe_capacity_ep_alltoall_composes_with_dp(cpu_devices):
    """data x expert mesh: the expert axis goes manual inside shard_map
    while the data axis stays GSPMD-automatic."""
    mesh = mesh_lib.make_mesh(cpu_devices, data=2, expert=4)
    mod, params = _capacity_moe()
    x = jnp.asarray(np.random.default_rng(6).normal(size=(4, 6, 8)),
                    jnp.float32)
    expected = np.asarray(mod.apply(x, M.Ctx(params)))
    sharded = sharding.shard_params(params, mesh)
    xs = jax.device_put(x, jax.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data")))
    out = jax.jit(lambda p, xb: mod.apply(xb, M.Ctx(p, ep_mesh=mesh)))(
        sharded, xs)
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)


def test_moe_capacity_ep_gradients_match(cpu_devices):
    """Param gradients through the two all_to_alls == replicated grads."""
    mesh = mesh_lib.make_mesh(cpu_devices[:4], expert=4)
    mod, params = _capacity_moe()
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 5, 8)),
                    jnp.float32)

    def loss(p, ctx_kw):
        return (mod.apply(x, M.Ctx(p, **ctx_kw)) ** 2).sum()

    want = jax.grad(lambda p: loss(p, {}))(params)
    sharded = sharding.shard_params(params, mesh)
    got = jax.jit(jax.grad(lambda p: loss(p, {"ep_mesh": mesh})))(sharded)
    for key in want:
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(want[key]),
                                   atol=2e-4, rtol=1e-4,
                                   err_msg=key)


def test_moe_capacity_ep_compiles_to_alltoall(cpu_devices):
    """The compiled HLO routes tokens via all-to-all and carries NO
    all-reduce of the full activation (the r04 EP census pathology: 34
    all-reduces, zero all-to-all — dense combine over the expert axis)."""
    mesh = mesh_lib.make_mesh(cpu_devices[:4], expert=4)
    mod, params = _capacity_moe()
    x = jnp.asarray(np.random.default_rng(8).normal(size=(2, 6, 8)),
                    jnp.float32)
    sharded = sharding.shard_params(params, mesh)
    fn = jax.jit(lambda p, xb: mod.apply(xb, M.Ctx(p, ep_mesh=mesh)))
    hlo = fn.lower(sharded, x).compile().as_text()
    assert "all-to-all" in hlo


def test_moe_capacity_ep_alltoall_composes_with_sp(cpu_devices):
    """sequence x expert mesh (the dryrun phase-1 shape): tokens arrive
    sequence-sharded on T; the group reshape + expert-axis shard_map must
    still produce the single-device result."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = mesh_lib.make_mesh(cpu_devices, sequence=2, expert=4)
    mod, params = _capacity_moe()
    x = jnp.asarray(np.random.default_rng(9).normal(size=(2, 8, 8)),
                    jnp.float32)
    expected = np.asarray(mod.apply(x, M.Ctx(params)))
    sharded = sharding.shard_params(params, mesh)
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "sequence")))
    out = jax.jit(lambda p, xb: mod.apply(xb, M.Ctx(p, ep_mesh=mesh)))(
        sharded, xs)
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)


# ---------------------------------------------------------------------------
# dropless dispatch, and a layer held as one rank's share
# ---------------------------------------------------------------------------

def _skewed(dispatch, **kw):
    """A layer whose router sends most rows to expert 2 and none to expert
    5 (the input's first feature is positive; the router leans on it)."""
    args = dict(in_features=16, intermediate_size=24, num_experts=8, top_k=3,
                shared_expert_size=8, shared_expert_gate=False,
                routed_scale=2.5)
    mod = M.MixtureOfExperts(dispatch=dispatch, **{**args, **kw})
    mod.bind("moe")
    params = mod.init(jax.random.key(0))
    router = 0.3 * np.array(params["moe.router.weight"])
    router[:, 0] = 0.0
    router[2, 0], router[5, 0] = 4.0, -60.0
    params["moe.router.weight"] = jnp.asarray(router)
    x = np.random.default_rng(0).normal(size=(2, 12, 16))
    x[..., 0] = np.abs(x[..., 0]) + 1.0
    return mod, params, jnp.asarray(x, jnp.float32)


def _loop_over_experts(mod, params, x):
    """Plain loop: every held expert on every token, weighted by what the
    router gave it; the ungated shared expert added."""
    p = lambda name: params[mod.key(name)]
    silu = lambda t: t * jax.nn.sigmoid(t)
    probs = jax.nn.softmax(x @ p("router.weight").T, axis=-1)
    w, e = jax.lax.top_k(probs, mod.top_k)
    w = mod.routed_scale * w / jnp.sum(w, -1, keepdims=True)
    out = (silu(x @ p("shared_expert.gate_proj.weight").T)
           * (x @ p("shared_expert.up_proj.weight").T)
           ) @ p("shared_expert.down_proj.weight").T
    for j in range(mod.experts_held):
        share = jnp.sum(jnp.where(e == mod.first_expert + j, w, 0.0), -1)
        y = (silu(x @ p("experts.gate_proj.weight")[j].T)
             * (x @ p("experts.up_proj.weight")[j].T)
             ) @ p("experts.down_proj.weight")[j].T
        out = out + share[..., None] * y
    return out


@pytest.fixture
def tile_of_4(monkeypatch):
    """Groups padded to 4 rows, so that a toy layer has whole tiles, ragged
    groups and several rounds of its row buffer (a row a token)."""
    monkeypatch.setattr(M.MixtureOfExperts, "ROW_TILE", 4)


# a share's arguments, ``dropless_plan(24)``'s (rows, rows_bound,
# rounds_bound, places) and the rounds the skewed router's rows take: every
# expert held (a token meets three of them: three rows a token, four
# rounds); a share of two experts, one of them the empty one, from expert 4
# on (held < top_k: a place an expert); the empty expert alone; five of the
# eight from expert 1 on (held > top_k: a place a choice)
SHARES = {
    "many_rounds": ({}, (24, 96, 4, 3), 4),
    "one_round": (dict(experts_held=2, first_expert=4), (24, 72, 3, 2), 1),
    "no_round": (dict(experts_held=1, first_expert=5), (24, 48, 2, 1), 0),
    "a_place_a_choice": (dict(experts_held=5, first_expert=1),
                         (24, 96, 4, 3), 3),
}


@pytest.mark.parametrize("share", list(SHARES))
def test_moe_dropless_equals_dense_and_a_loop_under_a_skewed_router(
        share, tile_of_4):
    """The dropless dispatch (the layout from compares and a cumulative sum,
    grouped products over the rows each expert really got, the rows read
    back by their tokens) computes what ``dense`` and a plain loop over the
    experts compute, forward and gradient (in ``x``, the router's weights
    and the three stacks), with one expert taking most rows and one none,
    for every share of ``SHARES``."""
    share, sizes, took = SHARES[share]
    dense, params, x = _skewed("dense", **share)
    dropless, _, _ = _skewed("dropless", **share)
    plan = dropless.dropless_plan(24)
    assert (plan["rows"], plan["rows_bound"], plan["rounds_bound"],
            plan["places"]) == sizes
    assert plan["combine"] == "take"                # the kernel is the TPU's
    ctx = M.Ctx(params)
    dropless.apply(x, ctx)
    assert -(-float(ctx.reported()["moe_rows_padded"]) // plan["rows"]) == took
    weight = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def loss(apply):
        return lambda p, x: jnp.sum(apply(p, x) * weight)

    by = {"dense": lambda p, x: dense.apply(x, M.Ctx(p)),
          "dropless": lambda p, x: dropless.apply(x, M.Ctx(p)),
          "loop": lambda p, x: _loop_over_experts(dense, p, x)}
    got = {name: jax.jit(jax.value_and_grad(loss(fn), (0, 1)))(params, x)
           for name, fn in by.items()}
    routed = np.asarray(dense.route(x, M.Ctx(params))[1]).ravel()
    loads = np.bincount(routed, minlength=8)
    assert loads[2] == x.shape[0] * x.shape[1] and loads[5] == 0
    for other in ("dense", "loop"):
        value, (dparams, dx) = got[other]
        assert float(got["dropless"][0]) == pytest.approx(float(value),
                                                          rel=1e-5)
        np.testing.assert_allclose(got["dropless"][1][1], dx, atol=2e-5)
        for name, want in dparams.items():
            np.testing.assert_allclose(got["dropless"][1][0][name], want,
                                       atol=2e-5, err_msg=name)


def _layout_of(mod, params, x):
    """The layer's own layout for ``x``, the weights beside it, and the
    round configuration ``_apply_dropless`` would walk it with."""
    tokens = x.shape[0] * x.shape[1]
    plan = mod.dropless_plan(tokens)
    top_vals, top_idx = mod.route(x, M.Ctx(params))
    hit, chosen, weight = M._held_choices(
        top_vals.reshape(tokens, -1), top_idx.reshape(tokens, -1),
        mod.first_expert, mod.experts_held)
    layout = M._dropless_layout(hit, chosen, plan["row_tile"],
                                plan["rows_bound"])
    cfg = M._DroplessConfig(
        row_tile=plan["row_tile"], rows=plan["rows"], held=mod.experts_held,
        activation=mod.activation, on_tpu=False, combine=plan["combine"])
    return cfg, plan, layout, weight, (top_vals, top_idx)


@pytest.mark.parametrize("share", list(SHARES))
def test_moe_dropless_layout_is_a_stable_counting_sort(share, tile_of_4):
    """The layout made of dense compares and one cumulative sum, and the
    rows each round finds in it, against a counting sort written out in
    NumPy over the (token, choice) pairs in their order: row -> token,
    row -> weight, the tile table, the groups' sizes, a token's rows place
    by place, and the four counters."""
    share, _, took = SHARES[share]
    mod, params, x = _skewed("dropless", **share)
    cfg, plan, layout, weight, (top_vals, top_idx) = _layout_of(mod, params, x)
    tile, bound, held = plan["row_tile"], plan["rows_bound"], mod.experts_held
    vals = np.asarray(top_vals).reshape(-1, mod.top_k)
    local = np.asarray(top_idx).reshape(-1, mod.top_k) - mod.first_expert
    sizes = np.array([(local == e).sum() for e in range(held)])
    ends = np.cumsum(-(-sizes // tile) * tile)
    row_token = np.full(bound, -1)
    row_weight = np.zeros(bound, np.float32)
    filled = ends - (-(-sizes // tile) * tile)      # a group's next free row
    rows_of = [[] for _ in range(local.shape[0])]
    for n, choices in enumerate(local):
        for k, e in enumerate(choices):
            if 0 <= e < held:
                row_token[filled[e]], row_weight[filled[e]] = n, vals[n, k]
                rows_of[n].append(filled[e])
                filled[e] += 1
    tile_group = np.searchsorted(ends, np.arange(bound // tile) * tile,
                                 side="right")
    np.testing.assert_array_equal(layout.sizes, sizes)
    np.testing.assert_array_equal(layout.tile_group, tile_group)
    places = np.asarray(layout.place_row)
    assert places.shape == (plan["places"], local.shape[0])
    for n, rows in enumerate(rows_of):
        assert sorted(places[:, n][places[:, n] >= 0]) == sorted(rows)
    got_token = np.full(bound, -1)
    got_weight = np.zeros(bound, np.float32)
    for j in range(plan["rounds_bound"]):
        rnd = M._round_rows(cfg, j, layout, weight)
        live, at = np.asarray(rnd.live), slice(j * cfg.rows, (j + 1) * cfg.rows)
        got_token[at] = np.where(live, rnd.tok, -1)
        got_weight[at] = rnd.weight
        assert not np.asarray(rnd.tok)[~live].any()
    np.testing.assert_array_equal(got_token, row_token)
    np.testing.assert_allclose(got_weight, row_weight, rtol=1e-6)
    assert -(-ends[-1] // cfg.rows) == took
    ctx = M.Ctx(params)
    mod.apply(x, ctx)
    assert {k: float(v) for k, v in ctx.reported().items()} == {
        "moe_rows": sizes.sum(), "moe_rows_padded": ends[-1],
        "moe_load_max": sizes.max(), "moe_dropped": 0}


@pytest.mark.parametrize("share", list(SHARES))
def test_moe_combine_kernel_reads_what_the_take_reads(share, tile_of_4):
    """``penroz_moe_combine`` (interpret mode) against the ``take`` form the
    CPU runs, round by round over the skewed layout: the same float32 sums
    on top of the same running ``y``, from rows of either dtype."""
    from penroz_tpu.ops.pallas import moe_combine
    mod, params, x = _skewed("dropless", **SHARES[share][0])
    cfg, plan, layout, weight, _ = _layout_of(mod, params, x)
    keys = jax.random.split(jax.random.key(5), 2)
    y = jax.random.normal(keys[0], (24, 16))
    for j in range(plan["rounds_bound"]):
        rnd = M._round_rows(cfg, j, layout, weight)
        for dtype in (jnp.float32, jnp.bfloat16):
            rows = jax.random.normal(keys[1], (cfg.rows, 16)).astype(dtype)
            want = M._rows_to_tokens(cfg, layout, rnd, rows, rnd.weight, y)
            lo, hi = M._token_runs(cfg, layout, rnd, 24)
            got = moe_combine.rows_to_tokens(
                rows, rnd.weight, rnd.tok, lo, hi, y,
                places=plan["places"], interpret=True)
            np.testing.assert_allclose(got, want, atol=1e-5)
        y = want


def test_moe_dropless_counts_what_the_router_chose_and_drops_nothing(
        tile_of_4):
    """``moe_rows`` is the count of (token, choice) pairs whose expert is
    held, by the router's own choices; ``moe_rows_padded`` every group
    padded to whole tiles; ``moe_load_max`` the fullest held expert;
    ``moe_dropped`` 0 — also when every token goes to held experts only
    (held = the router's whole width: no pair may be lost to the bound)."""
    for held, first in ((8, 0), (3, 2)):
        mod, params, x = _skewed("dropless", experts_held=held,
                                 first_expert=first)
        ctx = M.Ctx(params)
        mod.apply(x, ctx)
        chosen = np.asarray(mod.route(x, M.Ctx(params))[1]).ravel() - first
        loads = np.bincount(chosen[(chosen >= 0) & (chosen < held)],
                            minlength=held)
        stats = {k: float(v) for k, v in ctx.reported().items()}
        assert set(stats) == {stat.name for stat in mod.stats()}
        assert stats["moe_rows"] == loads.sum() > 0
        assert stats["moe_rows_padded"] == (-(-loads // 4) * 4).sum()
        assert stats["moe_load_max"] == loads.max()
        assert stats["moe_dropped"] == 0
    assert loads.sum() < 3 * x.shape[0] * x.shape[1]   # a share: some absent


def _sorted_rows(rounds_of: int, dtype, each: int = 64):
    """A layout as ``_apply_dropless`` makes it: two experts that every one
    of ``each`` tokens chose, so ``each`` rows an expert in tiles of 8,
    every row real, cut into rounds of ``rounds_of`` rows; inputs and
    weights positive, so that every row adds to a weight's gradient with
    the same sign."""
    experts, tile, d, h = 2, 8, 16, 24
    keys = jax.random.split(jax.random.key(3), 5)
    x = (0.5 + jnp.abs(jax.random.normal(keys[0], (each, d)))).astype(dtype)
    stacks = tuple(
        (0.2 + 0.1 * jax.random.uniform(k, (experts, *shape))).astype(dtype)
        for k, shape in zip(keys[1:4], ((h, d), (h, d), (d, h))))
    weight = jax.random.uniform(keys[4], (experts, each), jnp.float32,
                                0.5, 1.5)
    chosen = jnp.ones((experts, each), jnp.bool_)
    layout = M._dropless_layout(chosen[None], chosen, tile, experts * each)
    cfg = M._DroplessConfig(row_tile=tile, rows=rounds_of, held=experts,
                            activation="silu", on_tpu=False, combine="take")
    return cfg, x, weight, stacks, layout


def test_moe_dropless_sums_weight_gradients_over_rounds_in_float32():
    """Under bfloat16 compute an expert's weight gradient summed over the
    64 rounds its 512 rows take is the gradient of one round over all the
    rows to within the last cast: every row's products are the same in
    both, the running sum over the rounds is float32 and is cast once
    (summed in bfloat16, a running sum 64 times a round's share rounds
    most of the share away: it reads 0.8 % off where this reads 0.1)."""
    def grads(rounds_of):
        cfg, x, w, stacks, layout = _sorted_rows(
            rounds_of, jnp.bfloat16, each=512)
        rounds = jnp.int32(1024 // rounds_of)
        fn = lambda *s: jnp.sum(M._dropless_rows(
            cfg, x, w, *s, layout, rounds)[0].astype(jnp.float32))
        return jax.jit(jax.grad(fn, (0, 1, 2)))(*stacks)

    for one, many in zip(grads(1024), grads(8)):
        assert many.dtype == jnp.bfloat16
        one, many = one.astype(jnp.float32), many.astype(jnp.float32)
        assert float(jnp.linalg.norm(many - one)
                     / jnp.linalg.norm(one)) < 0.003


def test_moe_dropped_counts_rows_the_rounds_really_handed_to_the_products():
    """``_dropless_rows`` counts its second result as its rounds run: a
    real row (within its group's size) in a tile the products compute.  A
    loop that stops a round short, a real row under a tile marked empty,
    and a padding row are each not counted, so ``moe_dropped`` (pairs routed
    less rows placed) would read them."""
    cfg, x, w, stacks, layout = _sorted_rows(32, jnp.float32)
    placed = lambda layout, rounds: float(M._dropless_rows(
        cfg, x, w, *stacks, layout, jnp.int32(rounds))[1])
    assert placed(layout, 4) == 128
    assert placed(layout, 3) == 96                       # a round skipped
    assert placed(layout._replace(
        tile_group=layout.tile_group.at[-1].set(2)), 4) == 120   # a tile
    assert placed(layout._replace(
        sizes=layout.sizes.at[1].add(-5)), 4) == 123     # padding rows


@pytest.mark.parametrize("router,ranks", [("softmax", 4), ("sigmoid", 8)])
def test_moe_expert_shares_add_up_to_the_uncut_reference_block(
        tile_of_4, router, ranks):
    """Every rank of an expert-parallel layer, the program's dropless layer
    told its share (``experts_held``, ``first_expert``): their routed parts,
    with the shared expert counted once, are the uncut block of the plain
    reference (all 16 experts held).  Four ranks of four under the softmax
    router of ``benchmark/reference/laguna.py``; eight of two under the
    sigmoid router with a selection bias of ``benchmark/reference/xing.py``."""
    from benchmark.reference import laguna, xing
    d, h, experts = 16, 24, 16
    k, scale = (5, 2.5) if router == "softmax" else (4, 2.0)
    keys = jax.random.split(jax.random.key(1), 8)
    normal = lambda i, *shape: 0.3 * jax.random.normal(keys[i], shape)
    ref = {"router": normal(0, d, experts), "e_gate": normal(1, experts, d, h),
           "e_up": normal(2, experts, d, h), "e_down": normal(3, experts, h, d),
           "s_gate": normal(4, d, 8), "s_up": normal(5, d, 8),
           "s_down": normal(6, 8, d)}
    x = jax.random.normal(keys[7], (2, 9, d))
    bias = xing.router_bias(experts, 1)
    with jax.default_matmul_precision("highest"):
        if router == "softmax":
            want = laguna._sparse(ref, x, first=0, top_k=k, scale=scale,
                                  norm_topk=True, mm=jnp.matmul)
            how = {}
        else:
            want = xing._sparse(ref, x, tuple(bias), first=0, top_k=k,
                                scale=scale, norm_topk=True, mm=jnp.matmul)
            how = {"scoring": "sigmoid", "selection_bias": True,
                   "selection_bias_init": [float(b) for b in bias]}
        shared = laguna._swiglu(x, ref["s_gate"], ref["s_up"], ref["s_down"],
                                jnp.matmul)
        total = shared
        for rank in range(ranks):
            held = experts // ranks
            mod = M.MixtureOfExperts(
                in_features=d, intermediate_size=h, num_experts=experts,
                top_k=k, dispatch="dropless", routed_scale=scale,
                experts_held=held, first_expert=rank * held, **how)
            mod.bind("moe")
            mine = slice(rank * held, (rank + 1) * held)
            swap = lambda a: jnp.swapaxes(a[mine], 1, 2)
            total = total + mod.apply(x, M.Ctx({
                "moe.router.weight": ref["router"].T,
                "moe.experts.gate_proj.weight": swap(ref["e_gate"]),
                "moe.experts.up_proj.weight": swap(ref["e_up"]),
                "moe.experts.down_proj.weight": swap(ref["e_down"])},
                mod.init_buffers()))
    np.testing.assert_allclose(total, want, atol=1e-5)


TILE = 8


@pytest.mark.parametrize("tile_group", [
    [0, 0, 2, 3, 3, 3],         # ragged, expert 1 empty, all tiles live
    [1, 1, 1, 4, 4, 4],         # one expert, then tiles past the last row
    [4, 4, 4, 4],               # nothing routed at all
    [0, 1, 2, 3],               # a tile each
], ids=["ragged", "tail_empty", "all_empty", "one_tile_each"])
def test_moe_grouped_kernels_match_ragged_dot_in_interpret_mode(tile_group):
    """``penroz_moe_gmm_fwd`` / ``_bwd_dx`` / ``_bwd_dw`` run as jnp
    (interpret mode) against ``jax.lax.ragged_dot`` over the same buffer:
    groups of unlike length crossing tiles, an expert with no row, tiles
    past the last routed row (their rows zero, their gradient nothing)."""
    from penroz_tpu.ops.pallas import moe_gmm
    groups, k, n = 4, 32, 16
    tiles = jnp.asarray(tile_group, jnp.int32)
    keys = jax.random.split(jax.random.key(0), 3)
    lhs = jax.random.normal(keys[0], (TILE * len(tile_group), k))
    rhs = jax.random.normal(keys[1], (groups, n, k))
    cotangent = jax.random.normal(keys[2], (TILE * len(tile_group), n))
    kernel = lambda l, r: moe_gmm.grouped_matmul_kernel(
        l, r, tiles, row_tile=TILE, interpret=True)
    ragged = lambda l, r: moe_gmm.grouped_matmul_ragged(
        l, r, tiles, row_tile=TILE)
    np.testing.assert_allclose(kernel(lhs, rhs), ragged(lhs, rhs), atol=1e-4)
    grads = [jax.grad(lambda l, r: jnp.sum(fn(l, r) * cotangent), (0, 1))(
        lhs, rhs) for fn in (kernel, ragged)]
    for got, want in zip(*grads):
        np.testing.assert_allclose(got, want, atol=1e-4)
    empty = np.repeat(np.asarray(tile_group) >= groups, TILE)
    assert not np.asarray(kernel(lhs, rhs))[empty].any()


def test_moe_dropless_plan_says_how_the_rows_come_back():
    """``combine`` is read from the shapes and the platform alone: the
    share cell's layer (8 of 256 experts, 8 192 tokens of 3 072) takes the
    kernel on a TPU and ``take`` elsewhere; a layer that holds all 256
    experts, a width off the lanes and a token count off the tile take
    ``take`` on a TPU too (``moe_combine.fits``: the kernel's scalars and
    copies would not fit the core)."""
    plan = lambda tokens=8192, on_tpu=True, d=3072, **kw: M.MixtureOfExperts(
        in_features=d, intermediate_size=1024, top_k=10, dispatch="dropless",
        **{**dict(num_experts=256, experts_held=8), **kw}).dropless_plan(
            tokens, on_tpu)
    assert (plan()["places"], plan()["combine"]) == (8, "runs")
    assert plan(on_tpu=False)["combine"] == "take"
    assert (plan(experts_held=256)["places"],
            plan(experts_held=256)["combine"]) == (10, "take")
    assert plan(d=3000)["combine"] == plan(tokens=8200)["combine"] == "take"


def test_moe_share_and_dropless_dsl_validation():
    moe = lambda **kw: M.MixtureOfExperts(
        in_features=8, intermediate_size=8, num_experts=8, top_k=2, **kw)
    assert moe(experts_held=2, first_expert=6,
               dispatch="dropless").param_shapes()[
                   "experts.gate_proj.weight"] == (2, 8, 8)
    assert moe(experts_held=2).param_shapes()["router.weight"] == (8, 8)
    assert "shared_expert_gate.weight" not in moe(
        shared_expert_size=4, shared_expert_gate=False).param_shapes()
    for bad in (dict(experts_held=0), dict(experts_held=3, first_expert=6),
                dict(experts_held=2, dispatch="capacity"),
                dict(dispatch="droples")):
        with pytest.raises(ValueError):
            moe(**bad)
