"""The looped cell's own pieces on the CPU: the count of its work
(``lib/looped_costs.py``), its three readers on synthetic traces, and what
``kinds/train_looped.py`` takes from the program's ``penroz/loop_plan``
span.  (The cell's rehearsal is ``test_rehearse.py``'s, which runs every
cell of ``BENCHMARK.json``.)"""

import importlib.util
import json
import os

import pytest

from benchmark.kinds import train_looped
from benchmark.lib import kernel_costs, looped_costs, peaks, program_spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "ouro-train-4k-loop4"
PEAKS = peaks.peaks_for("TPU v5 lite")


def _read(name):
    path = os.path.join(HERE, "..", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _art(ops, **extra):
    return {"kind": "train", "peaks": PEAKS,
            "dims": {"d": 2048, "heads": 16, "head_dim": 128,
                     "vocab": 49152},
            "job": {"batch_size": 2, "block_size": 4096},
            "trace": {"planes": {"devices": {0: {"ops": ops}}, "spans": []},
                      "w0": 0.0, "w1": 1.0}, **extra}


def _call(name, result, start, seconds):
    return (f"%{name}.7 = {result} custom-call(a, b)", start, start + seconds)


OUT = "bf16[2,4096,2048]{2,1,0}"
# one layer application with its recomputation: the forward runs twice
SPLIT = [
    _call("jvp_penroz_flash_fwd_", OUT, 0.00, 0.0010),
    _call("checkpoint_penroz_flash_fwd", OUT, 0.10, 0.0010),
    _call("transpose_jvp_penroz_flash_bwd_delta__", "f32[2,16,4096]", 0.20,
          0.0002),
    _call("transpose_jvp_penroz_flash_bwd_dq__", OUT, 0.30, 0.0015),
    _call("transpose_jvp_penroz_flash_bwd_dkv__", f"({OUT}, {OUT})", 0.40,
          0.0020),
    _call("jvp_penroz_ce_fwd_", "(f32[8192,1], f32[8192,1])", 0.50, 0.0012),
    _call("checkpoint_penroz_ce_fwd", "(f32[8192,1], f32[8192,1])", 0.60,
          0.0012),
    _call("transpose_jvp_penroz_ce_bwd__", "bf16[8192,49152]", 0.70, 0.0024),
    ("%fusion.1 = bf16[2,4096,2048]{2,1,0} fusion(a)", 0.80, 0.90),
]
ONE_PASS = SPLIT[:3] + [_call("transpose_jvp_penroz_flash_bwd__",
                              f"({OUT}, {OUT}, {OUT})", 0.30, 0.0035)]


def test_flops_per_token_counts_every_application():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "ouro-2.6b-loop4-6l.json")))
    args = (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["num_hidden_layers"])
    steps, vocab = cfg["total_ut_steps"], cfg["vocab_size"]
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert per_layer == 51380224
    assert looped_costs.matmul_params_per_pass(*args, vocab) \
        == 6 * per_layer + 2048 * 49152 == 408944640
    total = looped_costs.flops_per_token(*args, steps, vocab, 4096)
    assert total == 6.0 * 4 * 408944640 + 12.0 * 24 * 2048 * 4096
    assert total == pytest.approx(12.23e9, rel=1e-3)
    # one pass of a one-layer stack is the plain accounting
    assert looped_costs.flops_per_token(64, 4, 16, 96, 1, 1, 512, 64) == \
        kernel_costs.model_flops_per_token(
            4 * 64 * 64 + 3 * 64 * 96 + 64 * 512, 1, 64, 64)


@pytest.mark.parametrize("ops,seconds", [(SPLIT, 0.0057), (ONE_PASS, 0.0057)],
                         ids=["split_backward", "one_pass_backward"])
def test_useful_flash_share_counts_the_backwards_calls(ops, seconds):
    cost = kernel_costs.flash_attention(2, 16, 4096, 128, 2)
    least = sum(kernel_costs.roofline_seconds(cost[k], PEAKS)[0]
                for k in ("fwd", "bwd"))
    got = _read("penroz_flash_roofline.useful")(_art(ops))
    # one backward, so one application's work; the forward's second call is
    # time and no work
    assert got == pytest.approx(100.0 * least / seconds)
    assert 0.0 < got < 100.0


def test_ce_share_counts_every_call_by_name():
    cost = looped_costs.cross_entropy(8192, 49152, 2)
    assert cost["fwd"]["bytes"] == 8192 * 49152 * 2
    assert cost["bwd"]["bytes"] == 2 * cost["fwd"]["bytes"]
    least = (2 * kernel_costs.roofline_seconds(cost["fwd"], PEAKS)[0]
             + kernel_costs.roofline_seconds(cost["bwd"], PEAKS)[0])
    assert kernel_costs.roofline_seconds(cost["fwd"], PEAKS)[1] == "memory"
    got = _read("penroz_ce_roofline")(_art(SPLIT))
    assert got == pytest.approx(100.0 * least / 0.0048)
    assert 0.0 < got < 100.0


@pytest.mark.parametrize("name", ["penroz_flash_roofline.useful",
                                  "penroz_ce_roofline"])
def test_a_program_that_names_no_such_kernel_reads_nothing(name):
    parent = [_call("jvp__", "(f32[8192,1], f32[8192,1])", 0.0, 0.001),
              _call("transpose_jvp___", "bf16[8192,49152]", 0.1, 0.002)]
    assert _read(name)(_art(parent)) is None
    assert _read(name)({"kind": "train", "trace": None}) is None


def test_recompute_share_reads_the_plans_counters():
    read = _read("loop_recompute_pct")
    plan = {"steps": 4, "layers": 6, "applications": 24,
            "recomputed_applications": 24, "cache_slots": 24}
    assert read({"loop_plan": plan}) == 100.0
    assert read({"loop_plan": {**plan, "recomputed_applications": 6}}) == 25.0
    assert read({"loop_plan": None}) is None and read({}) is None


def test_loop_plan_is_the_jobs_newest_span():
    span = lambda name, t, **meta: program_spans.Span(name, t, t, None, meta)
    art = {"kind": "train", "program_spans": [
        span("penroz/train_epoch", 0.0, epoch=1),
        span("penroz/loop_plan", 0.1, applications=24,
             recomputed_applications=24),
        span("penroz/loop_plan", 5.0, applications=24,
             recomputed_applications=12)]}
    assert train_looped.loop_plan(art) == {"applications": 24,
                                           "recomputed_applications": 12}
    assert train_looped.loop_plan({"kind": "train",
                                   "program_spans": []}) is None
    assert train_looped.loop_plan({"kind": "train",
                                   "program_spans": None}) is None


def test_the_cell_is_listed_where_its_readers_are():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b-loop4-6l", "pretrain_4k_looped", 1)
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", [])}
    assert {"penroz_flash_roofline.useful", "penroz_ce_roofline",
            "loop_recompute_pct", "train_mfu_pct", "train_step_ms",
            "hbm_peak_gb.train"} <= listed
    assert "penroz_flash_roofline" not in listed
    for name in listed:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py")), name


def test_the_controls_at_a_tiny_size_fall_on_either_side_of_the_cells_limit():
    """The reference put in the program's place one precision down (scaled
    fp8) fails the cell's gradient limit; in the precision the
    configuration states (bfloat16) it passes.  (At the cell's own size, on
    the chip: PERF.md section 2.)"""
    import jax.numpy as jnp
    import numpy as np
    from benchmark.kinds.train import token_stream
    from benchmark.reference import ouro as ref
    from benchmark.run import deep_update
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "ouro-2.6b-loop4-6l.json")))
    limit = cfg["correct"]["grad_rel_err"]
    cfg = deep_update(cfg, cfg["rehearse"])
    d, job = ref.dims(cfg), cfg["train"]
    n = job["gradient_accumulation_steps"] * job["batch_size"]
    stream = token_stream(7, d["vocab"],
                          n * job["block_size"] + 1).astype(np.int32)
    xs = jnp.asarray(stream[:-1].reshape(n, job["block_size"]))
    ys = jnp.asarray(stream[1:].reshape(n, job["block_size"]))
    weights = ref.init_params(cfg, 7)
    kw = dict(heads=d["heads"], rows=job["reference_rows"])
    _, grad = ref.mean_loss_and_grad(weights, xs, ys, **kw)
    want = ref.as_gpt2_custom(grad, d["depth"])
    err = {}
    for precision in ("bfloat16", "fp8"):
        _, got = ref.mean_loss_and_grad(weights, xs, ys, precision=precision,
                                        **kw)
        err[precision] = ref.tree_rel_error(
            ref.as_gpt2_custom(got, d["depth"]), want)
    assert err["bfloat16"] < limit < err["fp8"], err
