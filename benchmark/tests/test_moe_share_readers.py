"""The share cell's own pieces on the CPU: the count of its work
(``lib/moe_share_costs.py``), its three readers on a synthetic trace and span
tree, what ``kinds/train_moe_share.py`` takes from the program's spans, the
reference (``reference/laguna.py``) against the program at the rehearsal
size, and the fp8 control failing there.  (The cell's rehearsal
is ``test_rehearse.py``'s, which runs every cell of ``BENCHMARK.json``.)"""

import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from benchmark.kinds import train_moe_share
from benchmark.lib import kernel_costs, moe_share_costs, peaks, program_spans
from benchmark.reference import laguna as ref
from benchmark.run import deep_update

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "laguna-train-8k-ep32share"
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "laguna-s-2.1-ep32-5l.json")
PEAKS = peaks.peaks_for("TPU v5 lite")
SEED = 2**31 + 77      # the driver's seeds do not fit 32 signed bits


def _read(name):
    path = os.path.join(HERE, "..", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _cfg(rehearse=False):
    cfg = json.load(open(CONFIG))
    return deep_update(cfg, cfg["rehearse"]) if rehearse else cfg


def test_flops_per_token_counts_the_share_and_only_the_rows_routed():
    """By hand at the cell's sizes: layer 0 full attention on 6 heads with a
    whole dense MLP, three sliding layers on 9 heads and one full on 6, each
    with router, shared expert and 0.3125 routed rows a token; the head
    over 12 544 ids; scores over 8192 keys or a window of 512."""
    d = ref.dims(_cfg())
    D, T = 3072, 8192
    attn = lambda h: D * ((h + 2) * 128 + h) + h * 128 * D
    sparse = D * 256 + 3 * D * 1024
    params = (attn(6) + 3 * D * 12288 + 3 * (attn(9) + sparse)
              + attn(6) + sparse + D * 12544)
    rows = 4 * 10 * 8 / 256
    assert moe_share_costs.matmul_params_per_token(d, rows) == \
        params + rows * 3 * D * 1024
    scores = 2 * 6 * 128 * T + 3 * 9 * 128 * 512
    assert moe_share_costs.flops_per_token(d, T, rows) == pytest.approx(
        6.0 * (params + rows * 3 * D * 1024) + 12.0 * scores)
    # no row routed: the routed experts count nothing
    assert moe_share_costs.flops_per_token(d, T, 0.0) == pytest.approx(
        6.0 * params + 12.0 * scores)
    # what the cut distorts (the configuration's reduced_why): layer 0 is
    # near half of the forward FLOPs (262.7 M of 535.8 M a token), the
    # shared expert outweighs the routed ones three to one
    layer0 = 2 * (attn(6) + 3 * D * 12288) + 4 * 6 * 128 * T
    whole = 2 * (params + rows * 3 * D * 1024) + 4 * scores
    assert layer0 / whole == pytest.approx(0.49, abs=0.005)
    assert 3 * D * 1024 / (0.3125 * 3 * D * 1024) == pytest.approx(3.2)


def test_grouped_products_least_time_follows_the_rows():
    d = ref.dims(_cfg())
    cost = moe_share_costs.grouped_products(2560, 1, 8, 3072, 1024, 2)
    assert cost["flops"] == 2 * 2560 * 3 * 3072 * 1024
    assert cost["bytes"] == 2 * (2560 * (3 * 3072 + 3 * 1024)
                                 + 3 * 8 * 3072 * 1024)
    least = moe_share_costs.grouped_least_seconds(2560, 1, d, PEAKS)
    assert least == pytest.approx(
        3 * kernel_costs.roofline_seconds(cost, PEAKS)[0])
    # the weights' bytes bound it at few rows, the FLOPs at many
    assert kernel_costs.roofline_seconds(cost, PEAKS)[1] == "memory"
    many = moe_share_costs.grouped_products(65536, 1, 8, 3072, 1024, 2)
    assert kernel_costs.roofline_seconds(many, PEAKS)[1] == "compute"
    assert moe_share_costs.grouped_least_seconds(0, 4, d, PEAKS) > 0


def _span(name, t0, t1=None, **meta):
    return program_spans.Span(name, t0, t0 if t1 is None else t1, None, meta)


def _epoch(t0, rows, padded, load, dropped=0, tokens=8192, took=0.2):
    return _span("penroz/train_epoch", t0, t0 + took, tokens=tokens,
                 epoch=round(10 * t0), moe_rows=rows, moe_rows_padded=padded,
                 moe_load_max=load, moe_dropped=dropped)


PLAN = {"experts": 256, "held": 8, "first": 0, "top_k": 10, "rows": 8192,
        "row_tile": 128, "dispatch": "dropless", "rows_bound": 73728,
        "rounds_bound": 9}


def _art(spans, ops=(), trace_spans=()):
    return {"kind": "train", "peaks": PEAKS, "dims": ref.dims(_cfg()),
            "job": {"batch_size": 1, "block_size": 8192},
            "micro_steps_per_epoch": 1,
            "window": SimpleNamespace(t0=10.0, t1=20.0),
            "program_spans": spans,
            "trace": {"planes": {"devices": {0: {"ops": list(ops)}},
                                 "spans": list(trace_spans)},
                      "w0": 0.0, "w1": 1.0}}


SPANS = [_span("penroz/moe_plan", 1.0, **{**PLAN, "rows": 2048}),
         _span("penroz/moe_plan", 2.0, **PLAN),
         _epoch(9.9, 1, 1, 1),                  # cut by the window's start
         _epoch(11.0, 10000, 12800, 1500),
         _epoch(12.0, 12000, 14080, 2100),
         _epoch(19.9, 7, 7, 7),                 # cut by its end
         _epoch(21.0, 5000, 6000, 900, took=0.5),   # after it, after a save
         _epoch(21.5, 9000, 11000, 1400),           # the trace holds these
         _epoch(21.7, 11000, 13000, 1600, took=0.21),  # two whole
         _epoch(21.91, 6000, 7000, 800)]

# the same epochs on the profiler's clock (21.45 s behind the job's): the
# first cut by the trace's start, the last by its end
TWINS = [("penroz/train_epoch", -0.05, 0.04),
         ("penroz/train_epoch", 0.05, 0.25),
         ("penroz/train_epoch", 0.25, 0.46),
         ("penroz/train_epoch", 0.46, 1.20)]


def test_the_kind_sums_the_epochs_counters_and_takes_the_newest_plan():
    art = _art(SPANS)
    assert train_moe_share.moe_plan(art) == PLAN
    assert train_moe_share.routing(art) == {
        "moe_rows": 22000, "moe_rows_padded": 26880, "moe_load_max": 3600,
        "moe_dropped": 0, "epochs": 2, "tokens": 16384}
    assert train_moe_share.traced_routing(art) is None   # no twin: no trace
    traced = train_moe_share.traced_routing(_art(SPANS, (), TWINS))
    assert traced.pop("misfit_ms") == pytest.approx(0.0, abs=1e-6)
    assert traced == {
        "moe_rows": 20000, "moe_rows_padded": 24000, "moe_load_max": 3000,
        "moe_dropped": 0, "epochs": 2, "tokens": 16384,
        "epoch_numbers": [215, 217]}
    bare = _art([_span("penroz/train_epoch", 11.0, 11.2, tokens=8192)])
    assert train_moe_share.routing(bare) is None
    assert train_moe_share.moe_plan(bare) is None
    assert train_moe_share.moe_plan(_art(None)) is None


def test_padding_and_load_read_the_windows_counters():
    art = _art(SPANS)
    art.update(moe=train_moe_share.routing(art),
               moe_plan=train_moe_share.moe_plan(art))
    assert _read("moe_pad_rows_pct")(art) == pytest.approx(
        100.0 * (26880 - 22000) / 22000)
    assert _read("moe_load_max_over_mean")(art) == pytest.approx(
        3600 * 8 / 22000)
    for name in ("moe_pad_rows_pct", "moe_load_max_over_mean"):
        assert _read(name)({"moe": None, "moe_plan": None}) is None
        assert _read(name)({}) is None


def _call(name, start, seconds):
    return (f"%{name}.7 = bf16[4096,1024]{{1,0}} custom-call(a, b)", start,
            start + seconds)


def test_grouped_share_counts_only_rows_really_routed():
    """Two epochs whole in the trace, 9 000 and 11 000 rows by the
    program's count of those very epochs (neither the 5 000 of the epoch
    before them nor the 6 000 of the one after): least time for 20 000 rows
    over 2 x 4 layer calls, over the device time of every call named
    ``penroz_moe_gmm_*`` (another kernel's and a fusion's time left out)."""
    ops = [_call("jvp_penroz_moe_gmm_fwd_", 0.10, 0.004),
           _call("checkpoint_penroz_moe_gmm_fwd", 0.20, 0.004),
           _call("transpose_jvp_penroz_moe_gmm_bwd_dx__", 0.30, 0.005),
           _call("transpose_jvp_penroz_moe_gmm_bwd_dw__", 0.40, 0.007),
           _call("jvp_penroz_flash_fwd_", 0.50, 0.010),
           ("%fusion.1 = bf16[4096,3072]{1,0} fusion(a)", 0.60, 0.70)]
    trace_spans = TWINS
    art = _art(SPANS, ops, trace_spans)
    art["moe_traced"] = train_moe_share.traced_routing(art)
    least = moe_share_costs.grouped_least_seconds(
        9000 + 11000, 2 * 4 * 1, art["dims"], PEAKS)
    got = _read("penroz_moe_gmm_roofline")(art)
    assert got == pytest.approx(100.0 * least / 0.020)
    assert 0 < got < 100
    # a program that names no such kernel, or counts no rows: nothing
    assert _read("penroz_moe_gmm_roofline")(
        {**art, "trace": {**art["trace"], "planes": {
            "devices": {0: {"ops": ops[4:]}}, "spans": trace_spans}}}) is None
    assert _read("penroz_moe_gmm_roofline")(
        {**art, "moe_traced": None}) is None
    assert _read("penroz_moe_gmm_roofline")({"kind": "train"}) is None


def test_the_cell_is_listed_where_its_readers_are():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s-2.1-ep32-5l", "pretrain_8k_moe_share", 1)
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", [])}
    assert {"penroz_moe_gmm_roofline", "moe_pad_rows_pct",
            "moe_load_max_over_mean", "train_mfu_pct", "train_step_ms",
            "hbm_peak_gb.train", "device_idle_pct.train"} <= listed
    assert "penroz_ce_roofline" in listed       # the CE kernels run here
    assert not {n for n in listed if "flash" in n or "loop" in n}
    for name in listed:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py")), name
    (rate,) = [m for m in manifest["end_to_end"]
               if m["name"] == "train_tokens_per_s"]
    assert CELL in rate["workloads"]


def test_the_configuration_holds_every_published_width_and_states_the_share():
    cfg = _cfg()
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_routed"], cfg["num_experts_per_tok"],
            cfg["sliding_window"], cfg["moe_routed_scaling_factor"]) == (
        3072, 128, 12288, 1024, 1024, 256, 10, 512, 2.5)
    yarn = cfg["rope_parameters"]["full_attention"]
    assert (yarn["rope_type"], yarn["factor"], yarn["beta_fast"],
            yarn["beta_slow"], yarn["original_max_position_embeddings"],
            yarn["partial_rotary_factor"]) == ("yarn", 128, 32, 1, 8192, 0.5)
    assert (cfg["num_experts"], cfg["num_key_value_heads"],
            cfg["num_attention_heads_per_layer"], cfg["vocab_size"],
            cfg["num_hidden_layers"]) == (8, 1, [6, 9, 9, 9, 6], 12544, 5)
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["deployment"]["chips_per_layer"] == 32
    assert set(cfg["reduced"]) <= set(cfg["reduced_why"])
    assert cfg["assumed"] and cfg["correct"]["tolerance_why"]


@pytest.fixture(scope="module")
def model():
    import numpy as np
    from penroz_tpu.models import presets
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    cfg = _cfg(rehearse=True)
    weights = ref.init_params(cfg, SEED)
    args = ref.preset_args(cfg)
    m = NeuralNetworkModel("sharetest", Mapper(
        getattr(presets, ref.PRESET)(**args), cfg["optimizer"]))
    mapped = ref.init_program_weights(cfg, SEED)
    assert all(np.array_equal(mapped[k], v) for k, v in
               ref.as_gpt2_custom(weights, cfg["num_hidden_layers"]).items())
    assert {k: v.shape for k, v in mapped.items()} == \
           {k: v.shape for k, v in m.params.items()}
    m.params = dict(mapped)
    return m, weights, cfg


def _batch(cfg, seed):
    import jax.numpy as jnp
    import numpy as np
    from benchmark.kinds.train import token_stream
    block = cfg["train"]["block_size"]
    stream = token_stream(seed, cfg["vocab_size"], 2 * block + 1).astype(
        np.int32)
    return (jnp.asarray(stream[:-1].reshape(2, block)),
            jnp.asarray(stream[1:].reshape(2, block)))


def test_reference_and_program_agree_at_the_rehearsal_size(model):
    """Forward, loss and the whole gradient of one optimizer step, float32
    both sides on the CPU: rounding only — the same share (4 of 16 experts
    from the first, 2 or 3 query heads on one K/V head, a 512-id slice)."""
    import jax
    import numpy as np
    import optax
    m, weights, cfg = model
    d = ref.dims(cfg)
    xs, ys = _batch(cfg, SEED)
    got, _ = m.compute_output(np.asarray(xs).tolist())
    want = jax.nn.softmax(ref.forward(weights, xs, heads=d["heads"]),
                          -1)[:, -1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-8)
    fn = m.arch.train_epoch_fn(cfg["optimizer"], 2, remat=False,
                               compute_dtype=None, platform=m._placement)
    out = fn(m.params, m.opt_state, m.buffers, xs[:, None], ys[:, None],
             jax.random.key(0))
    grad = {k: np.asarray(v) / (1 - 0.9)
            for k, v in optax.tree_utils.tree_get(out[1], "mu").items()}
    want_loss, want_grad = ref.mean_loss_and_grad(
        weights, xs, ys, heads=d["heads"], rows=1)
    assert abs(float(out[3]) - want_loss) / want_loss < 1e-5
    assert ref.tree_rel_error(
        grad, ref.as_gpt2_custom(want_grad, d["depth"])) < 1e-4
    routed = {k: float(v) for k, v in out[5].items()}
    assert routed["moe_dropped"] == 0 and routed["moe_rows"] > 0


def test_the_fp8_control_fails_at_the_rehearsal_size():
    """The reference put in the program's place with every matmul operand
    rounded to scaled fp8 fails the rehearsal's gradient limit, and reads
    three times what it reads in plain bfloat16, which passes the cell's.
    (At five layers of width 64 fp8 reads 0.033, under the cell's own
    limit, which is set from the readings at the cell's size on the chip:
    fp8 0.131 and more, bfloat16 and the program 0.011; PERF.md section
    2.)"""
    cfg = _cfg()
    cell_limit = cfg["correct"]["grad_rel_err"]
    cfg = deep_update(cfg, cfg["rehearse"])
    d = ref.dims(cfg)
    xs, ys = _batch(cfg, 7)
    weights = ref.init_params(cfg, 7)
    kw = dict(heads=d["heads"], rows=1)
    _, grad = ref.mean_loss_and_grad(weights, xs, ys, **kw)
    want = ref.as_gpt2_custom(grad, d["depth"])
    err = {}
    for precision in ("bfloat16", "fp8"):
        _, got = ref.mean_loss_and_grad(weights, xs, ys, precision=precision,
                                        **kw)
        err[precision] = ref.tree_rel_error(
            ref.as_gpt2_custom(got, d["depth"]), want)
    assert err["fp8"] > cfg["correct"]["grad_rel_err"], err
    assert 3 * err["bfloat16"] < err["fp8"], err
    assert err["bfloat16"] < cell_limit, err
