"""The Mamba-2 mixer, the chunked scan, the latent experts and the
``presets.nemotron_h_custom`` model against ``benchmark/reference/
nemotron_h.py`` (plain ``jax.numpy``), at small sizes on the CPU with seeded
weights: the scan against the recurrence, each module alone, the shares that
add up, then the whole model's first optimizer step at the benchmark
configuration's ``rehearse`` sizes."""

import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as ref
from penroz_tpu.models import presets
from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import CompiledArch
from penroz_tpu.ops import modules as M
from penroz_tpu.ops import ssm

pytestmark = pytest.mark.runtime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "nemotron3-super-120b-ep64-11l.json")


def _cfg() -> dict:
    with open(CONFIG, encoding="utf-8") as f:
        return json.load(f)


def _rehearse_cfg(**over) -> dict:
    cfg = _cfg()
    small = dict(cfg["rehearse"])
    cfg["published"] = {**cfg["published"], **small.pop("published")}
    cfg.update(small)
    cfg.update(over)
    return cfg


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol,
                               rtol=tol)


# -- the chunked scan --------------------------------------------------------

def _scan_inputs(T, H, G, P=8, N=16, B=2, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    return {"x": jax.random.normal(k[0], (B, T, H, P)),
            "dt": jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 2.0),
            "A_log": jax.random.normal(k[2], (H,)),
            "Bm": jax.random.normal(k[3], (B, T, G, N)),
            "Cm": jax.random.normal(k[4], (B, T, G, N)),
            "D": 1 + 0.3 * jax.random.normal(k[5], (H,)),
            "w": jax.random.normal(k[6], (B, T, H, P))}


def _scan_loss(scan):
    """The mixer's use of a scan, A from A_log and the skip included, as a
    scalar: every input the mixer differentiates has a gradient."""
    def loss(x, dt, A_log, Bm, Cm, D, w):
        y = scan(x, dt, -jnp.exp(A_log), Bm, Cm)
        return jnp.sum((y + D[:, None] * x) * w)
    return loss


SCANS = {"T40_c8": (40, 8, 4, 2), "T64_c16_one_group": (64, 16, 4, 1),
         "T33_c16_padded": (33, 16, 2, 2), "T16_c64_short": (16, 64, 6, 3),
         "T128_c128_a_head_a_group": (128, 128, 2, 2)}


@pytest.mark.parametrize("case", list(SCANS))
def test_ssd_chunked_equals_the_recurrence_values_and_gradients(case):
    """``ssd_chunked`` against the token-by-token recurrence, the program's
    and the reference's: values and the gradients of x, Δ, A_log, B, C and
    D, for several T (no multiple of the chunk among them), chunk sizes and
    head and group counts."""
    T, chunk, H, G = SCANS[case]
    inputs = _scan_inputs(T, H, G)
    args = tuple(inputs[k] for k in ("x", "dt", "A_log", "Bm", "Cm", "D",
                                     "w"))
    chunked = lambda *a: ssm.ssd_chunked(*a, chunk)
    A = -jnp.exp(inputs["A_log"])
    want = ref.ssd_recurrence(inputs["x"], inputs["dt"], A, inputs["Bm"],
                              inputs["Cm"])
    _close(chunked(inputs["x"], inputs["dt"], A, inputs["Bm"], inputs["Cm"]),
           want, tol=1e-4)
    grads = lambda scan: jax.grad(_scan_loss(scan), argnums=range(6))(*args)
    for got, wanted, name in zip(grads(chunked), grads(ref.ssd_recurrence),
                                 ("x", "dt", "A_log", "B", "C", "D")):
        scale = float(jnp.max(jnp.abs(wanted)))
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(wanted) / scale, atol=2e-5,
                                   err_msg=name)


def test_ssd_chunked_with_a_state_dropped_at_one_boundary_is_caught(
        monkeypatch):
    """The planted fault: the state not carried over one chunk boundary (the
    third chunk starts from zero).  The comparison above must see it, in the
    values and in the gradients."""
    T, chunk, H, G = SCANS["T40_c8"]
    inputs = _scan_inputs(T, H, G)
    A = -jnp.exp(inputs["A_log"])
    call = lambda: ssm.ssd_chunked(inputs["x"], inputs["dt"], A,
                                   inputs["Bm"], inputs["Cm"], chunk)
    want = call()
    entering = ssm._ssd_entering_states
    monkeypatch.setattr(
        ssm, "_ssd_entering_states",
        lambda states, decay: entering(states, decay).at[:, 2].set(0.0))
    got = call()
    assert float(jnp.max(jnp.abs(got - want))) > 1e-2
    _close(got[:, :2 * chunk], want[:, :2 * chunk])         # before it: equal
    with pytest.raises(AssertionError):
        _close(got, ref.ssd_recurrence(inputs["x"], inputs["dt"], A,
                                       inputs["Bm"], inputs["Cm"]), tol=1e-4)


def test_ssd_refuses_shapes_that_do_not_fit_and_plans_its_boundaries():
    inputs = _scan_inputs(16, 4, 3)
    with pytest.raises(ValueError, match="do not fit"):
        ssm.ssd_chunked(inputs["x"], inputs["dt"], inputs["A_log"],
                        inputs["Bm"], inputs["Cm"], 8)
    plan = ssm.ssd_plan(4096, heads=16, groups=1, head_dim=64, state=128,
                        chunk=128)
    assert plan == {"chunks": 32, "padded": 0, "path": "chunked",
                    "boundary_bytes": 32 * 16 * 64 * 128 * 4}
    assert ssm.ssd_plan(100, 2, 1, 8, 16, 16)["padded"] == 12


def test_ssd_backward_keeps_boundary_states_and_no_state_a_token():
    """What the scan's backward holds between its forward and its backward:
    the inputs and the chunk-boundary states, nothing T × P × N."""
    T, chunk, H, G, P, N = 256, 16, 2, 1, 8, 16
    inputs = _scan_inputs(T, H, G, B=1)
    A = -jnp.exp(inputs["A_log"])
    _, pull = jax.vjp(lambda x: ssm.ssd_chunked(
        x, inputs["dt"], A, inputs["Bm"], inputs["Cm"], chunk), inputs["x"])
    kept = [leaf.size for leaf in jax.tree.leaves(pull)
            if hasattr(leaf, "size")]
    boundary = (T // chunk) * H * P * N
    assert max(kept) == boundary and boundary < T * H * P * N // 8


# -- the mixer ---------------------------------------------------------------

def _mixer(d=48, heads=4, held=None, first=0, groups=2, P=8, N=16, chunk=16):
    mod = M.Mamba2Mixer(d, heads, P, N, n_groups=groups, chunk_size=chunk,
                        heads_held=held, first_head=first)
    return mod.bind("m")


def _mixer_weights(key, d, H, G, P, N, conv=4):
    k = jax.random.split(key, 8)
    d_in, bc = H * P, G * N
    normal = lambda i, *shape: 0.3 * jax.random.normal(k[i], shape)
    return {"w_in": normal(0, d, 2 * d_in + 2 * bc + H),
            "conv_w": normal(1, conv, d_in + 2 * bc),
            "conv_b": normal(2, d_in + 2 * bc), "dt_bias": normal(3, H),
            "a_log": normal(4, H), "skip": 1 + normal(5, H),
            "gain": 1 + normal(6, d_in), "w_out": normal(7, d_in, d)}


def _as_program(h, prefix="m"):
    return {f"{prefix}.in_proj.weight": h["w_in"].T,
            f"{prefix}.conv1d.weight": h["conv_w"].T,
            f"{prefix}.conv1d.bias": h["conv_b"],
            f"{prefix}.dt_bias": h["dt_bias"], f"{prefix}.A_log": h["a_log"],
            f"{prefix}.D": h["skip"], f"{prefix}.norm.weight": h["gain"],
            f"{prefix}.out_proj.weight": h["w_out"].T}


def _ref_mixer(h, u, H, G, P=8, N=16):
    return ref.mamba_mixer(h, u, heads=H, head_dim=P, groups=G, state=N,
                           conv=4, eps=1e-5, mm=jnp.matmul)


@pytest.mark.parametrize("T", [32, 27])
def test_mamba2_mixer_matches_the_reference(T):
    """Forward and every gradient (A_log, dt_bias, D, the convolution and
    the gated norm's gain among them), at a T that is and one that is no
    multiple of the chunk."""
    d, H, G = 48, 4, 2
    mod = _mixer(d, H, groups=G)
    h = _mixer_weights(jax.random.key(1), d, H, G, 8, 16)
    u = jax.random.normal(jax.random.key(2), (2, T, d))
    w = jax.random.normal(jax.random.key(3), (2, T, d))
    ours = lambda h, u: jnp.sum(mod.apply(u, M.Ctx(_as_program(h))) * w)
    theirs = lambda h, u: jnp.sum(_ref_mixer(h, u, H, G) * w)
    _close(mod.apply(u, M.Ctx(_as_program(h))), _ref_mixer(h, u, H, G),
           tol=1e-4)
    got = jax.grad(ours, argnums=(0, 1))(h, u)
    want = jax.grad(theirs, argnums=(0, 1))(h, u)
    for (path, g), wanted in zip(jax.tree_util.tree_leaves_with_path(got),
                                 jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(wanted))) or 1.0
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(wanted) / scale, atol=5e-5,
                                   err_msg=str(path))


def test_mamba2_head_shares_add_up_to_the_whole_mixer():
    """The shares add up: a mixer of 8 heads in 4 groups cut 4 ways by
    groups, each share holding its heads' columns of W_in, channels of the
    convolution, dt_bias, A_log, D, gain and rows of W_out, sums to the
    whole mixer's output (the reference's, uncut)."""
    d, H, G, P, N, T = 32, 8, 4, 8, 16, 24
    whole = _mixer_weights(jax.random.key(5), d, H, G, P, N)
    u = jax.random.normal(jax.random.key(6), (1, T, d))
    want = _ref_mixer(whole, u, H, G)
    d_in, bc, per = H * P, G * N, H // G
    total = 0.0
    for g in range(G):
        hs = slice(g * per, (g + 1) * per)
        ch = slice(g * per * P, (g + 1) * per * P)
        cols = np.r_[np.arange(d_in)[ch], d_in + np.arange(d_in)[ch],
                     2 * d_in + g * N + np.arange(N),
                     2 * d_in + bc + g * N + np.arange(N),
                     2 * d_in + 2 * bc + np.arange(H)[hs]]
        conv = cols[per * P:-per] - d_in
        share = {"w_in": whole["w_in"][:, cols],
                 "conv_w": whole["conv_w"][:, conv],
                 "conv_b": whole["conv_b"][conv],
                 "dt_bias": whole["dt_bias"][hs], "a_log": whole["a_log"][hs],
                 "skip": whole["skip"][hs], "gain": whole["gain"][ch],
                 "w_out": whole["w_out"][ch]}
        mod = _mixer(d, H, held=per, first=g * per, groups=G)
        assert mod.plan(1, T)["held"] == per and mod.groups_held == 1
        total = total + mod.apply(u, M.Ctx(_as_program(share)))
    _close(total, want, tol=1e-4)


def test_mamba2_share_bounds_are_refused_when_heads_do_not_divide():
    for bad in (dict(heads=6, groups=4), dict(held=3), dict(held=2, first=1),
                dict(held=4, first=2), dict(held=0)):
        with pytest.raises(ValueError, match="whole groups|no multiple"):
            _mixer(**{"heads": 4, "groups": 2, **bad})
    assert _mixer(heads=4, groups=2, held=2, first=2).param_shapes()[
        "in_proj.weight"] == (2 * 16 + 2 * 16 + 2, 48)


def test_mamba2_refuses_a_cache_and_the_model_says_so_once():
    """The cached path's one refusal: the module's, and the model's before
    any cache is made (``/generate/`` → 400)."""
    cfg = _rehearse_cfg()
    arch = CompiledArch.get(presets.nemotron_h_custom(**ref.preset_args(cfg)))
    assert len(arch.mixers) == 5
    with pytest.raises(ValueError, match="Mamba-2 mixer .mamba2. does not "
                                         "run with a KV cache"):
        arch.kv_specs
    mod = _mixer()
    with pytest.raises(ValueError, match="no KV-cache path"):
        ctx = M.Ctx({}, kv=object())
        mod.apply(jnp.zeros((1, 4, 48)), ctx)


def test_ssd_plan_is_logged_once_spanned_per_trace_and_reports_its_counters(
        caplog, monkeypatch):
    """One INFO line a distinct plan, one ``penroz/ssd_plan`` span each time
    a program traces the mixer, under whatever span is compiling, and the
    two counters of a training call."""
    from penroz_tpu.utils import tracing
    mod = _mixer()
    h = _mixer_weights(jax.random.key(1), 48, 4, 2, 8, 16)
    # the server's log_config.json, once a test of this worker has loaded
    # it, keeps the package's records from the root logger caplog hears
    monkeypatch.setattr(logging.getLogger("penroz_tpu"), "propagate", True)
    M._log_plan.cache_clear()
    tracing.reset()
    trace = tracing.maybe_trace("ssd-plan-job", job=True, route="/train/")
    ctx = M.Ctx(_as_program(h), training=True)
    with caplog.at_level(logging.INFO, logger=M.__name__), \
            tracing.use(trace), tracing.span("penroz/train_dispatch"):
        for _ in range(2):
            mod.apply(jnp.ones((1, 40, 48)), ctx)
    dispatch = trace.to_dict()["root"]["children"][0]
    spans = [c["meta"] for c in dispatch["children"]
             if c["name"] == "penroz/ssd_plan"]
    assert spans == [mod.plan(1, 40)] * 2
    trace.finish("completed")
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("ssd plan:")]
    assert lines == ["ssd plan: heads=4 held=4 groups=2 state=16 head_dim=8 "
                     "chunk=16 conv_kernel=4 T=40 path=chunked "
                     f"boundary_bytes={4 * 3 * 4 * 8 * 16}"]
    got = ctx.reported()
    assert set(got) == {"ssd_dt_max", "ssd_log_decay_absmax"}
    assert 0 < float(got["ssd_dt_max"]) < 20
    assert float(got["ssd_log_decay_absmax"]) > float(got["ssd_dt_max"]) * 0.1


# -- the latent experts ------------------------------------------------------

def _latent_layer(key, d=32, latent=16, width=24, shared=40, experts=16):
    k = jax.random.split(key, 7)
    normal = lambda i, *shape: 0.3 * jax.random.normal(k[i], shape)
    return {"router": normal(0, d, experts), "down": normal(1, d, latent),
            "up": normal(2, latent, d), "e_w1": normal(3, experts, latent,
                                                       width),
            "e_w2": normal(4, experts, width, latent),
            "s_w1": normal(5, d, shared), "s_w2": normal(6, shared, d)}


def _moe_params(h, held=slice(None), prefix="e"):
    swap = lambda t: jnp.swapaxes(t, 1, 2)
    return {f"{prefix}.router.weight": h["router"].T,
            f"{prefix}.experts.up_proj.weight": swap(h["e_w1"][held]),
            f"{prefix}.experts.down_proj.weight": swap(h["e_w2"][held]),
            f"{prefix}.latent_down.weight": h["down"].T,
            f"{prefix}.latent_up.weight": h["up"].T,
            f"{prefix}.shared_expert.up_proj.weight": h["s_w1"].T,
            f"{prefix}.shared_expert.down_proj.weight": h["s_w2"].T}


def _latent_moe(bias, dispatch="dropless", **share):
    mod = M.MixtureOfExperts(
        32, 24, 16, top_k=6, activation="relu2", latent=16,
        shared_expert_size=40, shared_expert_gate=False, dispatch=dispatch,
        routed_scale=5.0, scoring="sigmoid", selection_bias=True,
        selection_bias_init=list(bias), **share)
    return mod.bind("e")


@pytest.mark.parametrize("dispatch", ["dropless", "dense"])
def test_latent_relu2_experts_match_the_reference(dispatch):
    """LatentMoE, all experts held: two stacks and no gate, the routed sum
    in the latent, the shared expert at the full width; forward and every
    gradient (the router, both latent projections, both stacks)."""
    h = _latent_layer(jax.random.key(7))
    bias = ref.router_bias(16, 0)
    mod = _latent_moe(bias, dispatch)
    assert "experts.gate_proj.weight" not in mod.param_shapes()
    assert mod.dropless_plan(48)["latent"] == 16
    assert mod.dropless_plan(48)["activation"] == "relu2"
    u = jax.random.normal(jax.random.key(8), (2, 24, 32))
    w = jax.random.normal(jax.random.key(9), (2, 24, 32))
    theirs = lambda h, u: ref.latent_moe(
        h, u, tuple(map(float, bias)), first=0, top_k=6, scale=5.0,
        norm_topk=True, mm=jnp.matmul)
    ours = lambda h, u: mod.apply(
        u, M.Ctx(_moe_params(h), mod.init_buffers()))
    _close(ours(h, u), theirs(h, u), tol=1e-4)
    got = jax.grad(lambda h, u: jnp.sum(ours(h, u) * w), (0, 1))(h, u)
    want = jax.grad(lambda h, u: jnp.sum(theirs(h, u) * w), (0, 1))(h, u)
    for (path, g), wanted in zip(jax.tree_util.tree_leaves_with_path(got),
                                 jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(wanted))) or 1.0
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(wanted) / scale, atol=5e-5,
                                   err_msg=str(path))


def test_latent_expert_shares_add_up_with_the_shared_expert_counted_once():
    """The shares add up: the 8 expert shares of one LatentMoE layer (2 of
    16 experts each), with the shared expert and nothing else counted once,
    sum to the uncut reference's layer.  What every rank computes alike is
    the shared expert; the routed part goes through ``W_up``, which is
    linear, so the ranks' latent partial sums add after it as before it."""
    h = _latent_layer(jax.random.key(11))
    bias = ref.router_bias(16, 2)
    u = jax.random.normal(jax.random.key(12), (1, 40, 32))
    want = ref.latent_moe(h, u, tuple(map(float, bias)), first=0, top_k=6,
                          scale=5.0, norm_topk=True, mm=jnp.matmul)
    shared = jnp.matmul(jnp.square(jax.nn.relu(jnp.matmul(u, h["s_w1"]))),
                        h["s_w2"])
    total = shared
    for rank in range(8):
        mod = _latent_moe(bias, experts_held=2, first_expert=2 * rank)
        out = mod.apply(u, M.Ctx(_moe_params(h, slice(2 * rank, 2 * rank + 2)),
                                 mod.init_buffers()))
        total = total + (out - shared)
    _close(total, want, tol=1e-4)


def test_relu2_is_refused_where_the_dispatch_computes_gated_experts():
    with pytest.raises(ValueError, match="relu2"):
        M.MixtureOfExperts(8, 8, 4, activation="relu2", dispatch="capacity")
    with pytest.raises(ValueError, match="latent"):
        M.MixtureOfExperts(8, 8, 4, latent=-1)


# -- the whole model ---------------------------------------------------------

def test_nemotron_preset_reads_the_pattern_and_counts_the_cells_parameters():
    """The preset from the published keys: one mixerblock a letter of the
    pattern, and the parameter count at the cell's size equal to the
    configuration's table (700.9 M to its four digits), to the file's
    ``parameters_held`` and to the benchmark's hand count
    (``lib/ssm_share_costs.py::parameters``)."""
    cfg = _cfg()
    layers = presets.nemotron_h_custom(**ref.preset_args(cfg))
    kinds = [next(iter(entry["mixerblock"]["mixer"])) for entry in layers[1:-3]]
    assert kinds == ["mamba2", "moe"] * 4 + ["mamba2", "sequential", "moe"]
    from benchmark.lib import ssm_share_costs
    count = presets.param_count(layers)
    hand = ssm_share_costs.parameters(ref.dims(cfg))
    assert count == cfg["parameters_held"] == hand["all"] == 700_862_960
    assert f"{count / 1e6:.1f}" == "700.9"
    assert (hand["M"], hand["E"], hand["*"], hand["expert"]) == (
        13_704_496, 98_566_144, 5_242_880, 5_505_024)
    with pytest.raises(ValueError, match="M, E and"):
        presets.nemotron_h_custom(**{**ref.preset_args(cfg),
                                     "pattern": "ME-"})
    with pytest.raises(ValueError, match="whole groups"):
        CompiledArch.get(presets.nemotron_h_custom(
            **{**ref.preset_args(cfg), "mamba_heads_held": 12}))
    published = cfg["published"]["hybrid_override_pattern"]
    assert published[27:38] == cfg["hybrid_override_pattern"]
    assert (published.count("M"), published.count("E"),
            published.count("*")) == (40, 40, 8)


def test_nemotron_first_optimizer_step_matches_the_reference_at_rehearse_sizes():
    """Loss and whole gradient of the first optimizer step (the epoch
    program ``PUT /train/`` runs, its gradient read back from AdamW's first
    moment as the benchmark's spy reads it) against the reference, and the
    counters the epoch returns."""
    import optax
    cfg = _rehearse_cfg()
    d = ref.dims(cfg)
    layers = presets.nemotron_h_custom(**ref.preset_args(cfg))
    mapper = Mapper(layers, cfg["optimizer"])
    arch = CompiledArch.get(layers)
    shapes, _ = jax.eval_shape(lambda: mapper.init_params(arch.mods, seed=0))
    _, buffers = mapper.init_params(arch.mods, seed=0)
    params = ref.init_program_weights(cfg, 11)
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
    weights = ref.init_params(cfg, 11)
    loss, grads = ref.mean_loss_and_grad(
        weights, xs.reshape(steps, -1), ys.reshape(steps, -1),
        heads=d["heads"], rows=1)
    want = {k: np.asarray(v) for k, v in
            ref.as_gpt2_custom(grads, d["depth"]).items()}
    assert set(got) == set(want)
    assert abs(float(out[3]) - loss) / loss < 1e-5
    assert ref.tree_rel_error(got, want) < cfg["correct"]["grad_rel_err"]
    assert all(np.abs(want[k]).max() > 0 for k in want), "a dead gradient"
    stats = out[5]
    assert float(stats["moe_dropped"]) == 0
    assert float(stats["moe_rows"]) > 0
    assert 0 < float(stats["ssd_dt_max"]) < 1.0
    assert float(stats["ssd_log_decay_absmax"]) > 0
    assert float(stats["moe_bias_absmax"]) == pytest.approx(max(
        float(np.abs(ref.router_bias(d["experts"], i)).max())
        for i, kind in enumerate(d["pattern"]) if kind == "E"))


def test_nemotron_trains_through_the_model_and_generate_refuses(workdir,
                                                                toy_shards):
    """The normal path at a toy size: ``train_model`` (what ``PUT /train/``
    runs) trains the preset's DSL, its sampled progress rows carry the
    scan's two counters beside the routing ones, ``/evaluate/``'s uncached
    forward works, and generation refuses with the one error (→ 400)."""
    from penroz_tpu.models.model import NeuralNetworkModel
    cfg = _rehearse_cfg(vocab_size=64)
    layers = presets.nemotron_h_custom(**ref.preset_args(cfg))
    model = NeuralNetworkModel("nemo1", Mapper(layers, cfg["optimizer"]))
    model.train_model("toy", shard=0, epochs=3, batch_size=2, block_size=16,
                      step_size=1)
    assert model.status["code"] == "Trained"
    row = model.progress[-1]
    assert row["ssd_dt_max"] > 0 and row["ssd_log_decay_absmax"] > 0
    assert row["moe_dropped"] == 0 and row["moe_bias_absmax"] > 0
    cost = model.evaluate_model("toy", None, shard=0, epochs=1, batch_size=2,
                                block_size=16, step_size=1)
    assert np.isfinite(cost)
    with pytest.raises(ValueError, match="Mamba-2 mixer"):
        model.generate_tokens([[1, 2]], block_size=16, max_new_tokens=2,
                              temperature=0.0)


# -- the cell's layers, compiled for the chip --------------------------------

@pytest.mark.parametrize("kind", ["M", "E"])
def test_cell_layers_compile_for_v5e_and_the_benchmark_finds_them(kind):
    """One M and one E layer of the cell as it holds them (1 x 4096 tokens, d
    4096, 16 of 128 Mamba heads, 8 of 512 experts of width 2688 in a latent
    of 1024, bf16), loss and gradient, compiled for a described v5e.  The E
    layer's grouped products are the accepted kernels, two stacks a phase
    (2688 is no multiple of the 512-column tile: ``moe_gmm._tile`` takes
    384, and the whole width would not fit the core); the M layer has no
    kernel of its own, and ``benchmark/metrics/ssd_time_pct.py`` tells its
    scan and convolution by their results' shapes, in the M layer and in no
    operation of the E layer."""
    import importlib.util
    import re
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from penroz_tpu.models import dsl
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"TPU topology cannot be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = _cfg()
    arch = CompiledArch.get(presets.nemotron_h_custom(
        **{**ref.preset_args(cfg), "pattern": kind,
           "router_bias": None}))
    shapes, bufs = jax.eval_shape(
        lambda: dsl.init_module_params(arch.mods, seed=0))
    spec = lambda tree, dtype=None: {
        k: jax.ShapeDtypeStruct(v.shape, dtype or v.dtype, sharding=chip)
        for k, v in tree.items()}
    x = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=chip)

    def loss(p, b, x, y):
        _, cost, _, _ = arch.forward(p, b, x, y, training=True,
                                     skip_softmax=True,
                                     compute_dtype=jnp.bfloat16,
                                     platform="tpu")
        return cost

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        hlo = jax.jit(jax.grad(loss)).lower(
            spec(shapes, jnp.bfloat16), spec(bufs), x, x).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
    path = os.path.join(ROOT, "benchmark", "metrics", "ssd_time_pct.py")
    module_spec = importlib.util.spec_from_file_location("ssd_time", path)
    reader = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(reader)
    plan = M.Mamba2Mixer(**presets.nemotron_h_custom(
        **ref.preset_args(cfg))[1]["mixerblock"]["mixer"]["mamba2"]).plan(
            1, 4096)
    sig = reader.signatures(plan, 1)
    instructions = [line.strip().removeprefix("ROOT ")
                    for line in hlo.splitlines() if " = " in line]
    found = [i for i in instructions if reader.is_mixer_op(i, sig)]
    calls = re.findall(r"%(\w*penroz_\w+?)[.\d]* = ", hlo)
    if kind == "M":
        assert not [c for c in calls if "penroz_ce" not in c], calls
        assert sum(i.split(" = ")[0].startswith("%fusion") or "fusion" in
                   i.split(" = ")[0] for i in found) >= 8, found[:5]
        assert any(i.startswith("%while") for i in found)
    else:
        assert not found, found[:5]
        for name in ("penroz_moe_gmm_fwd", "penroz_moe_gmm_bwd_dx",
                     "penroz_moe_gmm_bwd_dw", "penroz_moe_combine"):
            assert any(name in c for c in calls), (name, calls)
        assert "bf16[8,2688,1024]" in hlo and "bf16[8,1024,2688]" in hlo
