"""The plain reference against the program at a tiny size on the CPU, and the
controls: the same comparison, with the reference computed one precision
below what a configuration states, must come out clearly worse.  On the
chip the same comparison runs at the published widths, outside the window
(``kinds/*.py::compare_with_reference``; ``tools/control.py`` for the
controls)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gpt2 as ref

CFG = {"n_embd": 64, "n_head": 4, "n_layer": 2, "vocab_size": 512,
       "n_positions": 64}
OPT = {"adamw": {"lr": 6e-4, "betas": [0.9, 0.95], "eps": 1e-8,
                 "weight_decay": 0.1}}
SEED = 2**31 + 77      # the driver's seeds do not fit 32 signed bits


@pytest.fixture(scope="module")
def model():
    from penroz_tpu.models import presets
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    weights = ref.init_params(CFG, SEED)
    args = ref.preset_args(CFG)
    m = NeuralNetworkModel("reftest", Mapper(
        getattr(presets, ref.PRESET)(**args), OPT))
    mapped = ref.init_program_weights(CFG, SEED)
    assert all(np.array_equal(mapped[k], v) for k, v in
               ref.as_gpt2_custom(weights, args["depth"]).items())
    assert {k: v.shape for k, v in mapped.items()} == \
           {k: v.shape for k, v in m.params.items()}
    m.params = dict(mapped)
    return m, weights


def test_weights_come_from_the_seed_alone():
    a = ref.init_params(CFG, SEED)
    b = ref.init_params(CFG, SEED)
    c = ref.init_params(CFG, SEED + 1)
    assert all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not np.array_equal(a["wte"], c["wte"])
    assert abs(float(jnp.std(a["wte"])) - 0.02) < 2e-3
    assert float(jnp.std(a["h0"]["proj_w"])) < 0.02 / 1.9   # 1/sqrt(2·depth)


def test_forward_agrees_with_the_program(model):
    m, weights = model
    tokens = np.random.default_rng(0).integers(0, 512, (3, 64))
    got, _ = m.compute_output(tokens.tolist())
    want = jax.nn.softmax(ref.logits(weights, jnp.asarray(tokens),
                                     heads=4), -1)[:, -1]
    # float32 both sides on the CPU: rounding only
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-8)


def _first_step(m, xs, ys):
    """What ``lib/spy.py`` reads on the chip: loss and gradient of one
    optimizer step, the gradient from AdamW's first moment."""
    import optax
    fn = m.arch.train_epoch_fn(OPT, xs.shape[0], remat=False,
                               compute_dtype=None, platform=m._placement)
    out = fn(m.params, m.opt_state, m.buffers, xs, ys, jax.random.key(0))
    mu = optax.tree_utils.tree_get(out[1], "mu")
    return float(out[3]), {k: np.asarray(v) / (1 - 0.9)
                           for k, v in mu.items()}


def test_loss_and_gradient_agree_and_the_controls_do_not(model):
    m, weights = model
    rng = np.random.default_rng(1)
    xs = rng.integers(0, 512, (4, 3, 64)).astype(np.int32)
    ys = rng.integers(0, 512, (4, 3, 64)).astype(np.int32)
    loss, grad = _first_step(m, xs, ys)
    flat = lambda a: jnp.asarray(a.reshape(-1, a.shape[-1]))
    want_loss, want = ref.mean_loss_and_grad(weights, flat(xs), flat(ys),
                                             heads=4, rows=3)
    want = ref.as_gpt2_custom(want, 2)
    sound = ref.tree_rel_error(grad, want)
    assert abs(loss - want_loss) / want_loss < 1e-5
    assert sound < 1e-4
    # the controls: bf16 (below float32) and scaled fp8 (below bfloat16)
    readings = {}
    for precision in ("bfloat16", "fp8"):
        _, g = ref.mean_loss_and_grad(weights, flat(xs), flat(ys), heads=4,
                                      rows=3, precision=precision)
        readings[precision] = ref.tree_rel_error(
            ref.as_gpt2_custom(g, 2), want)
    assert readings["bfloat16"] > 3e-3 > 3 * sound
    assert readings["fp8"] > 3 * readings["bfloat16"]


def test_greedy_regret_is_zero_for_the_reference_and_not_for_noise():
    weights = ref.init_params(CFG, SEED)
    prompt = [int(t) for t in
              np.random.default_rng(2).integers(0, 512, 10)]
    own = ref.greedy_continue(weights, prompt, 12, heads=4, block=64,
                              precision="float32")
    assert ref.greedy_regret(weights, prompt, own, heads=4,
                             block=64).max() == 0.0
    wrong = [(t + 1) % 512 for t in own]
    assert ref.greedy_regret(weights, prompt, wrong, heads=4,
                             block=64).mean() > 0.5
    with pytest.raises(ValueError):
        ref.greedy_regret(weights, prompt, own * 6, heads=4, block=64)


def test_the_serving_controls_read_above_the_reference_itself():
    """``tools/control.py``'s serving control at a size a test can hold: the
    reference decoding greedily in a lower precision, scored as a run scores
    the served tokens (``kinds/serve_open.py``), and each precision's own
    choice read without decoding (``chosen_by``).  Float32 reads 0, scaled
    fp8 over the limit, bfloat16 (the control of a float32 configuration)
    between.  On the chip bfloat16 reads under every limit the program
    passes, which is why no serving cell stands yet (PERF.md, Open
    questions, first)."""
    import json
    import os
    from benchmark.kinds.serve_open import regret_numbers, sample_requests
    from benchmark.lib import traffic
    from helpers import ROOT
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "gpt2-large-hf.json")))
    limits = cfg["rehearse"]["correct"]
    mix = {"rate_per_s": 1.0,
           "prompt_tokens": {"median": 12, "sigma": .6, "min": 4, "max": 24},
           "output_tokens": {"median": 12, "sigma": .6, "min": 4, "max": 24}}
    reqs = traffic.schedule(mix, SEED, 12, 512, 64)
    sample = sample_requests(reqs, SEED, 6)
    assert len(sample) == 6 and len({id(r) for r in sample}) == 6
    assert sample[0].max_new == max(r.max_new for r in reqs)   # the longest
    assert sample_requests(reqs, SEED, 6) == sample             # from the seed
    assert sample_requests(reqs, SEED + 1, 6)[1:] != sample[1:]
    weights = ref.init_params(CFG, SEED)
    kw = dict(heads=4, block=64)
    got, forced = {}, {}
    for precision in ("float32", "bfloat16", "fp8"):
        regrets, choices = [], []
        for r in sample:
            tokens = ref.greedy_continue(weights, r.prompt, r.max_new,
                                         precision=precision, **kw)
            regrets.append(ref.greedy_regret(weights, r.prompt, tokens, **kw))
            # without decoding: the precision's own choice at each position
            # of the sequence the float32 reference continues the prompt with
            own = ref.greedy_continue(weights, r.prompt, r.max_new,
                                      precision="float32", **kw)
            choices.append(ref.greedy_regret(weights, r.prompt, own,
                                             chosen_by=precision, **kw))
        got[precision] = regret_numbers(regrets)
        forced[precision] = regret_numbers(choices)
    for reading in (got, forced):
        assert reading["float32"]["greedy_regret_mean"] == 0.0
        assert reading["float32"]["greedy_regret_max"] == 0.0
        assert reading["fp8"]["greedy_regret_mean"] \
            > limits["greedy_regret_mean"]
        assert reading["fp8"]["greedy_regret_mean"] \
            >= reading["bfloat16"]["greedy_regret_mean"]
    assert regret_numbers([])["greedy_regret_mean"] == float("inf")
