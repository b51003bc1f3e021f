"""The one channel from a layer to the training step (ops/modules.py::Stat):
what a module declares to report of a training call reaches the epoch
program's sixth result, the ``penroz/train_epoch`` span, the ``/progress/``
row and ``GET /metrics`` by its declaration alone, folded by the declared
rule over the modules of a call and the micro-steps of an epoch.  A toy
module defined here, and the tiny models of each preset family."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing
from penroz_tpu.models import dsl, presets
from penroz_tpu.models import model as model_mod
from penroz_tpu.models.dsl import Mapper
from penroz_tpu.models.model import CompiledArch
from penroz_tpu.ops import modules as M
from penroz_tpu.utils.metrics import _fmt_value

from test_train_trace import App, named

pytestmark = pytest.mark.runtime

SGD = {"sgd": {"lr": 0.1}}
VOCAB, BLOCK = 32, 8


class Probe(M.Module):
    """Passes its input on and reports its mean: under one statistic of
    each rule (and its negative under ``max``, for the smaller micro-step's),
    how many of its values are positive as a count, a pair a pass."""

    TOTAL = M.Stat("probe_total", "sum", "moe")
    POSITIVE = M.Stat("probe_positive", "sum", "moe", host=int)
    HIGH = M.Stat("probe_high", "max", "hc")
    NEG_LOW = M.Stat("probe_neg_low", "max", "hc")
    MEAN = M.Stat("probe_mean", "mean", "hc")
    PASSES = M.Stat("probe_passes", "mean", "exit_mass", shape=(2,))

    def stats(self):
        return (self.TOTAL, self.POSITIVE, self.HIGH, self.NEG_LOW,
                self.MEAN, self.PASSES)

    def apply(self, x, ctx):
        mean = jnp.mean(x.astype(jnp.float32))
        for stat, value in ((self.TOTAL, mean), (self.POSITIVE,
                                                 jnp.sum(x > 0)),
                            (self.HIGH, mean), (self.NEG_LOW, -mean),
                            (self.MEAN, mean),
                            (self.PASSES, jnp.stack([mean, 2 * mean]))):
            ctx.report(stat, value)
        return x


LAYERS = [
    {"embedding": {"num_embeddings": VOCAB, "embedding_dim": 8}},
    {"probe": {}},
    {"linear": {"in_features": 8, "out_features": VOCAB}},
    {"softmaxlast": {"dim": -1}},
]


@pytest.fixture
def probe(monkeypatch):
    """The toy module under a name of the layer DSL: the one thing a test
    must do that a module of ``ops/modules.py`` has done for it."""
    monkeypatch.setitem(dsl._LEAF_ALGOS, "probe", Probe)


def _want(table, xs) -> dict:
    a, b = (float(np.mean(table[x])) for x in xs)
    return {"probe_total": a + b, "probe_positive": int((table[xs] > 0).sum()),
            "probe_high": max(a, b), "probe_neg_low": -min(a, b),
            "probe_mean": (a + b) / 2,
            "probe_passes": [(a + b) / 2, a + b]}


def test_a_declared_statistic_leaves_both_epoch_programs_by_its_rule(probe):
    """Two accumulated micro-steps of known tokens: the sum doubles, the
    max is the larger micro-step's, the mean the average; the fused epoch
    and the micro-stepped pair return the same sixth result."""
    mapper = Mapper(LAYERS, SGD)
    arch = CompiledArch.get(mapper.layers)
    assert set(arch.step_stats) == {s.name for s in Probe().stats()}
    xs = np.stack([np.full((2, BLOCK), 3), np.arange(2 * BLOCK)
                   .reshape(2, BLOCK) + 7]).astype(np.int32)
    ys = (xs + 1) % VOCAB
    fresh = lambda: mapper.init_params(arch.mods, seed=0)
    state = lambda p: dsl.build_optimizer(SGD).init(p)
    key = jax.random.key(0)
    params, buffers = fresh()
    fused = arch.train_epoch_fn(SGD, 2)(params, state(params), buffers, xs,
                                        ys, key)
    params, buffers = fresh()
    micro, finalize = arch.train_micro_fns(SGD, 2)
    grads = jax.tree.map(jnp.zeros_like, params)
    bufs, cost = buffers, arch.zero_cost_sum()
    for i in range(2):
        bufs, grads, cost = micro(params, bufs, grads, cost, xs[i], ys[i],
                                  key, i)
    stepped = finalize(params, state(params), grads, bufs, cost)
    want = _want(np.asarray(params["layers.0.weight"]), xs)
    for out in (fused, stepped):
        assert len(out) == 6 and set(out[5]) == set(want)
        got = {k: arch.step_stats[k].on_host(v) for k, v in out[5].items()}
        assert all(got[k] == pytest.approx(want[k], rel=1e-5) for k in want)
        assert type(got["probe_positive"]) is int
        assert type(got["probe_mean"]) is float


def test_modules_of_one_call_fold_by_the_rule_and_through_recomputation():
    """Three reports of a call, two of them from inside ``_recomputed``:
    the sum of all, the largest, the mean of the three."""
    probes = [Probe().bind(f"p{i}") for i in range(3)]
    x = [jnp.full((1, 4), v, jnp.float32) for v in (2, 9, 4)]
    ctx = M.Ctx({}, training=True, rng=jax.random.key(0))
    probes[0].apply(x[0], ctx)

    def inside(inner, a, b):
        return probes[1].apply(a, inner), probes[2].apply(b, inner)
    M._recomputed(inside, ctx, probes[1:], x[1], x[2])
    got = {k: np.asarray(v).tolist() for k, v in ctx.reported().items()}
    assert got == {
        "probe_total": 15.0, "probe_positive": 12.0, "probe_high": 9.0,
        "probe_neg_low": -2.0, "probe_mean": 5.0,
        "probe_passes": [5.0, 10.0]}


@pytest.mark.parametrize("declared", [
    Probe.TOTAL._replace(reduce="max"), Probe.TOTAL._replace(reduce="min"),
    Probe.TOTAL._replace(family="probes")],
    ids=["two_rules_one_name", "unknown_rule", "unknown_family"])
def test_a_model_whose_declarations_cannot_be_kept_is_refused(
        probe, monkeypatch, declared):
    """One declaration a name, a rule the fold knows, a family ``/metrics``
    lists: else the model is refused when it is built, not an epoch in."""
    class Other(Probe):
        def stats(self):
            return (declared,)
    monkeypatch.setitem(dsl._LEAF_ALGOS, "other", Other)
    layers = LAYERS if declared.reduce == "max" else LAYERS[:1] + LAYERS[2:]
    with pytest.raises(ValueError, match="probe_total"):
        CompiledArch(Mapper([*layers, {"other": {}}], SGD).layers)


@pytest.mark.parametrize("microstepped", [False, True],
                         ids=["fused", "microstepped"])
def test_a_toy_modules_statistics_reach_span_row_and_metrics(
        probe, tmp_path, monkeypatch, microstepped):
    """``POST /model/`` → ``PUT /train/`` of a model with the toy module,
    two micro-steps an epoch, with no edit anywhere but the module: every
    epoch's span carries the scalars under their names and the per-pass
    value as ``probe_passes_<t>``, the ``/progress/`` row the same values
    (the count an ``int``), ``/metrics`` the newest under the declared
    families; on the fused epoch and, with a decode pending, the
    micro-stepped one."""
    monkeypatch.setenv("PENROZ_DECODE_PRIORITY_MS", "1")
    app = App(tmp_path)
    try:
        app.create("probe", LAYERS)
        if microstepped:
            with model_mod.decode_priority():
                rid = app.train("probe", epochs=3, batch=2)
                progress = app.wait("probe")
        else:
            rid = app.train("probe", epochs=3, batch=2)
            progress = app.wait("probe")
        assert progress["status"]["code"] == "Trained", progress["status"]
        _, tree = app.call("GET", f"/trace/{rid}")
        _, scrape = app.call("GET", "/metrics")
    finally:
        app.close()
    epochs = named(tree, "penroz/train_epoch")
    assert len(epochs) == 3 == len(progress["progress"])
    for epoch, row in zip(epochs, progress["progress"]):
        meta = epoch["meta"]
        assert meta["microstepped"] is microstepped
        high, low = meta["probe_high"], -meta["probe_neg_low"]
        assert low <= high
        assert meta["probe_total"] == pytest.approx(high + low, rel=1e-6)
        assert meta["probe_mean"] == pytest.approx((high + low) / 2,
                                                   rel=1e-6)
        assert meta["probe_passes_1"] == pytest.approx(meta["probe_mean"])
        assert meta["probe_passes_2"] == pytest.approx(meta["probe_total"])
        assert type(meta["probe_positive"]) is int
        # two micro-steps of 2 x BLOCK tokens, 8 values a token
        assert 0 < meta["probe_positive"] < 2 * 2 * BLOCK * 8
        assert "probe_passes" not in meta
        assert row["probe_passes"] == [meta["probe_passes_1"],
                                       meta["probe_passes_2"]]
        assert all(row[k] == meta[k] and type(row[k]) is type(meta[k])
                   for k in ("probe_total", "probe_positive", "probe_high",
                             "probe_neg_low", "probe_mean"))
    last = epochs[-1]["meta"]
    for family, label, name, value in (
            ("moe", "counter", "probe_total", last["probe_total"]),
            ("moe", "counter", "probe_positive", last["probe_positive"]),
            ("hc", "counter", "probe_high", last["probe_high"]),
            ("hc", "counter", "probe_mean", last["probe_mean"]),
            ("exit_mass", "pass", "2", last["probe_passes_2"])):
        assert (f'penroz_train_{family}{{{label}="{name}"}} '
                f'{_fmt_value(value)}\n' in scrape), (family, name)


def _xing_layers():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "xing4.0-29b-a4b-ep8-5l.json")
    with open(path, encoding="utf-8") as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    return presets.xing_custom(**xing.preset_args(cfg))


ROUTED = {"moe_rows", "moe_rows_padded", "moe_load_max", "moe_dropped"}
FAMILIES = {
    "plain": (lambda: presets.gpt2_custom(d=16, heads=2, depth=2, vocab=32,
                                          block=BLOCK), set()),
    "looped": (lambda: presets.ouro_custom(
        d=16, heads=2, head_dim=8, intermediate=24, depth=2, steps=3,
        vocab=32), {"pass_loss", "exit_mass"}),
    "dropless": (lambda: presets.laguna_custom(
        d=16, head_dim=8, layer_types=["full_attention", "full_attention"],
        heads_per_layer=[2, 2], kv_heads=1, mlp_layer_types=["dense",
                                                             "sparse"],
        intermediate=32, num_experts=8, experts_held=4, first_expert=2,
        top_k=2, moe_intermediate=8, shared_intermediate=8, vocab=32,
        window=4, rope={"full_attention": {"rope_type": "default",
                                           "rope_theta": 10000,
                                           "partial_rotary_factor": 1}}),
        ROUTED),
    "multi_stream": (_xing_layers,
                     ROUTED | {"hc_sinkhorn_err", "moe_bias_absmax"}),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_sixth_result_holds_exactly_what_the_modules_declare(family):
    """What the epoch program returns, by its shapes alone: five results
    for a model that declares nothing, a sixth of exactly the declared
    names and shapes for one that does."""
    layers, names = FAMILIES[family]
    mapper = Mapper(layers(), SGD)
    arch = CompiledArch.get(mapper.layers)
    assert set(arch.step_stats) == names
    assert set(arch.zero_cost_sum()) == names | {"cost"}
    params, buffers = jax.eval_shape(
        lambda: mapper.init_params(arch.mods, seed=0))
    opt_state = jax.eval_shape(dsl.build_optimizer(SGD).init, params)
    batch = jax.ShapeDtypeStruct((2, 1, 16), jnp.int32)
    out = jax.eval_shape(
        arch.train_epoch_fn(SGD, 2, with_ratios=False), params, opt_state,
        buffers, batch, batch, jax.eval_shape(lambda: jax.random.key(0)))
    if not names:
        assert len(out) == 5
        return
    assert len(out) == 6
    assert {k: v.shape for k, v in out[5].items()} == {
        name: arch.step_stats[name].shape for name in names}
    assert all(v.dtype == jnp.float32 for v in out[5].values())
