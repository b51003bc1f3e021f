"""The state-space share cell's own pieces on the CPU: the count of its work
(``lib/ssm_share_costs.py``) against hand counts at the cell's sizes, its
three readers on synthetic artefacts, and what ``kinds/train_ssm_share.py``
takes from the program's spans.  (The cell's rehearsal is
``test_rehearse.py``'s, which runs every cell of ``BENCHMARK.json``; the
program against ``reference/nemotron_h.py`` is ``tests/test_nemotron.py``'s.)
"""

import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from benchmark.kinds import train_ssm_share
from benchmark.lib import peaks, ssm_share_costs
from benchmark.reference import nemotron_h as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "nemotron3-super-120b-ep64-11l.json")
PEAKS = peaks.peaks_for("TPU v5 lite")
PLAN = {"heads": 128, "held": 16, "groups": 1, "state": 128, "head_dim": 64,
        "chunk": 128, "conv_kernel": 4, "T": 4096, "path": "chunked",
        "boundary_bytes": 16_777_216}


def _module(name):
    path = os.path.join(HERE, "..", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _cfg():
    with open(CONFIG, encoding="utf-8") as f:
        return json.load(f)


def test_costs_count_the_cells_forward_by_hand():
    """By hand at the cell's sizes (ISSUE 51's table): five mixers of 16
    heads (W_in 4096 x 2320, W_out 1024 x 4096), five expert layers (router
    512 wide, two latent projections of 1024, a shared expert of 5376; a
    routed row meets 2 x 1024 x 2688), one attention layer of 4 query heads
    on 1 key/value head of 128, the head over 16 384 ids."""
    d = ref.dims(_cfg())
    D, T = 4096, 4096
    mixer = D * 2320 + 1024 * D
    expert_layer = D * 512 + 2 * D * 1024 + 2 * D * 5376
    attention = D * 6 * 128 + 4 * 128 * D
    params = 5 * mixer + 5 * expert_layer + attention + D * 16384
    assert ssm_share_costs.mixer_params(d) == mixer
    assert ssm_share_costs.matmul_params_per_token(d, 0.0) == params
    rows = 5 * 22 * 8 / 512                # five E layers, 0.34 a layer
    routed = rows * 2 * 1024 * 2688
    assert ssm_share_costs.matmul_params_per_token(d, rows) == params + routed
    scan = 1 * 128 * 128 + 16 * 64 * 128 + 4 * 16 * 64 * 128
    assert ssm_share_costs.scan_forward_flops_per_token(d) == scan
    fwd = ssm_share_costs.forward_flops_per_token(d, T, rows)
    assert fwd == pytest.approx(2.0 * (params + routed) + 2 * 4 * 128 * T
                                + 5 * scan)
    # as ISSUE 51 reckoned it: 857 MFLOP forward a token, the shared
    # experts alone 440 M, the routed experts 19 M, the head 134 M
    assert fwd == pytest.approx(857e6, rel=5e-3)
    assert 5 * 2 * 2 * D * 5376 == pytest.approx(440e6, rel=2e-3)
    assert 2 * routed == pytest.approx(19e6, rel=1e-2)
    assert ssm_share_costs.flops_per_token(d, T, rows) == pytest.approx(
        6.0 * (params + routed) + 12 * 4 * 128 * T + 15 * scan)
    # the parameters held, counted by hand, are the file's
    held = ssm_share_costs.parameters(d)
    assert held["all"] == _cfg()["parameters_held"] == 700_862_960
    assert (held["M"], held["E"], held["*"], held["expert"]) == (
        13_704_496, 98_566_144, 5_242_880, 5_505_024)


def test_grouped_cost_is_two_products_at_the_latents_width():
    cost = ssm_share_costs.grouped_products(880, 5, 8, 1024, 2688, 2)
    assert cost["flops"] == 2 * 880 * 2 * 1024 * 2688
    assert cost["bytes"] == (880 * (2 * 1024 + 2 * 2688)
                             + 5 * 2 * 8 * 1024 * 2688) * 2
    d = ref.dims(_cfg())
    least = ssm_share_costs.grouped_least_seconds(880, 5, d, PEAKS)
    # at 176 rows an expert the bound is the experts' bytes
    assert least == pytest.approx(3 * cost["bytes"]
                                  / PEAKS["hbm_bytes_per_s"])
    scan = ssm_share_costs.scan(d, 4096)
    assert scan["bwd"]["flops"] == 2 * scan["fwd"]["flops"]
    assert ssm_share_costs.scan_least_seconds(d, 4096, PEAKS) > 0


def _trace(ops, busy=None):
    return {"planes": {"devices": {0: {"ops": ops}}, "spans": []},
            "w0": 0.0, "w1": 1.0, "devices": 1,
            "busy_s": busy if busy is not None else sum(
                b - a for _, a, b in ops)}


def test_latent_gmm_roofline_reads_the_traced_rows_and_cannot_pass_100():
    read = _module("penroz_moe_gmm_roofline.latent").read
    d = ref.dims(_cfg())
    moe = {"moe_rows": 3 * 880, "epochs": 3}
    least = ssm_share_costs.grouped_least_seconds(3 * 880, 15, d, PEAKS)
    call = lambda n: (f'%{n}.3 = bf16[4096,2688] custom-call(%a, %b), '
                      f'custom_call_target="tpu_custom_call"')
    names = ["penroz_moe_gmm_fwd", "jvp_penroz_moe_gmm_fwd_",
             "transpose_jvp_penroz_moe_gmm_bwd_dx__",
             "transpose_jvp_penroz_moe_gmm_bwd_dw__"]
    ops = [(call(n), i * least / 4, (i + 1) * least / 4)
           for i, n in enumerate(names)]
    art = {"kind": "train", "peaks": PEAKS, "dims": d, "moe_traced": moe,
           "micro_steps_per_epoch": 1, "trace": _trace(ops)}
    assert read(art) == pytest.approx(100.0)
    art["trace"] = _trace(ops + [(call("penroz_moe_gmm_fwd"), 0.5,
                                  0.5 + least)])
    assert read(art) == pytest.approx(50.0)
    # a gated model's dims, no rows, no such kernel, no trace: nothing
    assert read({**art, "dims": {"d": 3072, "held": 8}}) is None
    assert read({**art, "moe_traced": None}) is None
    assert read({**art, "trace": _trace([(call("penroz_ce_fwd"), 0, 1e-3)])}) \
        is None
    assert read({**art, "trace": None}) is None


def test_ssd_time_counts_the_scans_and_the_convolutions_results_alone():
    mod = _module("ssd_time_pct")
    sig = mod.signatures(PLAN, 1)
    mine = [
        "%fusion.7 = f32[1,32,1,16,128,128]{5,4,3,2,1,0} fusion(%a), kind=kLoop",
        "%fusion.8 = bf16[512,128,64]{2,1,0} fusion(%a, %b), kind=kOutput",
        "%while.3 = (s32[], f32[1,16,64,128]{3,2,1,0}, f32[32,1,16,64,128]) "
        "while(%t), condition=%c, body=%b",
        "%reduce-window.2 = f32[1,32,128,1,16]{4,3,2,1,0} reduce-window(%x)",
        "%fusion.9 = (f32[1,4096,1280]{2,1,0}, f32[1280]{0}) fusion(%p)",
        "%fusion.10 = f32[1280,4]{1,0} fusion(%g, %x), kind=kInput"]
    others = [
        "%fusion.1 = bf16[1,4096,1024]{2,1,0} fusion(%x, %w), kind=kOutput",
        "%fusion.2 = pred[32,128,4096]{2,1,0} fusion(%pos, %rank)",
        "%fusion.3 = bf16[1,4096,2320]{2,1,0} fusion(%x, %w), kind=kOutput",
        "%fusion.4 = f32[4096,16384]{1,0} fusion(%h, %head), kind=kOutput",
        "%fusion.5 = s32[32,128]{1,0} fusion(%starts)",
        '%penroz_ssd.1 = f32[1,32,128,16,64] custom-call(%x), '
        'custom_call_target="tpu_custom_call"']
    assert all(mod.is_mixer_op(name, sig) for name in mine)
    assert not any(mod.is_mixer_op(name, sig) for name in others)
    # self time: a while's body is counted once, under its own events
    ops = ([(mine[2], 0.0, 0.4), (mine[0], 0.1, 0.2), (others[0], 0.2, 0.3)]
           + [(others[3], 0.5, 0.9)])
    art = {"kind": "train", "ssd_plan": PLAN, "job": {"batch_size": 1},
           "trace": _trace(ops, busy=0.8)}
    assert mod.read(art) == pytest.approx(100.0 * 0.3 / 0.8)
    # a program that records no plan (the parent), or no trace: nothing
    assert mod.read({**art, "ssd_plan": None}) is None
    assert mod.read({**art, "trace": None}) is None


def _span(name, t0, t1, **meta):
    return SimpleNamespace(name=name, t0=t0, t1=t1, meta=meta)


def test_peaks_and_the_plan_come_from_the_jobs_spans(monkeypatch):
    window = SimpleNamespace(t0=10.0, t1=20.0)
    spans = [
        _span("penroz/ssd_plan", 1.0, 1.0, **PLAN),
        _span("penroz/train_epoch", 5.0, 6.0, ssd_log_decay_absmax=99.0,
              ssd_dt_max=9.0),                           # before the window
        _span("penroz/train_epoch", 11.0, 12.0, ssd_log_decay_absmax=7.5,
              ssd_dt_max=0.11, moe_bias_absmax=0.3),
        _span("penroz/train_epoch", 12.0, 13.0, ssd_log_decay_absmax=8.25,
              ssd_dt_max=0.10, moe_bias_absmax=0.31),
        _span("penroz/train_epoch", 19.5, 20.5, ssd_log_decay_absmax=50.0)]
    monkeypatch.setattr(train_ssm_share.program_spans, "spans",
                        lambda art: spans)
    art = {"window": window}
    assert train_ssm_share.plan_of(art, "penroz/ssd_plan") == PLAN
    got = train_ssm_share.peaks(art)
    assert got == {"ssd_log_decay_absmax": 8.25, "ssd_dt_max": 0.11,
                   "moe_bias_absmax": 0.31, "epochs": 2}
    assert train_ssm_share.first_epoch(art) == {
        "epoch": None, "ssd_log_decay_absmax": 99.0, "ssd_dt_max": 9.0}
    read = _module("ssd_log_decay_absmax").read
    assert read({"peaks_counted": got}) == 8.25
    assert read({"peaks_counted": None}) is None
    assert read({"peaks_counted": {"moe_bias_absmax": 0.3}}) is None
    assert read({}) is None
    # a program that counts none (the parent): nothing, and no error
    monkeypatch.setattr(train_ssm_share.program_spans, "spans",
                        lambda art: [_span("penroz/train_epoch", 11.0, 12.0,
                                           moe_rows=5)])
    assert train_ssm_share.peaks(art) is None
    assert train_ssm_share.first_epoch(art) is None
