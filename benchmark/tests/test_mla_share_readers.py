"""The latent-attention share cell's own pieces on the CPU: the count of its
work (``lib/mla_share_costs.py``) against hand counts at the cell's sizes,
its two readers on synthetic artefacts, and what ``kinds/train_mla_share.py``
takes from the program's spans.  (The cell's rehearsal is
``test_rehearse.py``'s, which runs every cell of ``BENCHMARK.json``; the
program against ``reference/xing.py`` is ``tests/test_xing.py``'s.)"""

import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from benchmark.kinds import train_mla_share
from benchmark.lib import kernel_costs, mla_share_costs, peaks
from benchmark.reference import xing as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "xing-train-4k-ep8share"
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "xing4.0-29b-a4b-ep8-5l.json")
PEAKS = peaks.peaks_for("TPU v5 lite")


def _read(name):
    path = os.path.join(HERE, "..", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _dims():
    with open(CONFIG, encoding="utf-8") as f:
        return ref.dims(json.load(f))


def test_costs_count_the_cells_parameters_and_forward_by_hand():
    """By hand at the cell's sizes: five latent-attention layers of H heads
    (the five matrices), ten sub-blocks' Phi of 14 336 x 24, layer 0's SwiGLU
    of 9216, four sparse blocks (router over 64, a shared expert of 1024, 8
    held experts of 1024), embedding and head over 16 384 ids."""
    d = _dims()
    D, H, V = 3584, d["heads"], 16384
    latent = (D * 768 + 768 * H * 192 + D * 576 + 512 * H * 256
              + H * 128 * D)
    phi = 4 * D * 24
    sparse = D * 64 + 3 * D * 1024
    params = 5 * (latent + 2 * phi) + 3 * D * 9216 + 4 * sparse + D * V
    assert mla_share_costs.latent_params(d) == latent
    assert mla_share_costs.matmul_params_per_token(d, 0.0) == params
    rows = 4 * 4 * 8 / 64                  # four sparse layers, 0.5 a layer
    assert mla_share_costs.matmul_params_per_token(d, rows) == \
        params + rows * 3 * D * 1024
    held = 4 * 8 * 3 * D * 1024
    small = 5 * (2 * D + 768 + 512 + 2 * 27) + D
    assert mla_share_costs.parameters(d) == params + held + small + D * V
    with open(CONFIG, encoding="utf-8") as f:
        assert mla_share_costs.parameters(d) == json.load(f)[
            "parameters_held"]
    T = 4096
    assert mla_share_costs.forward_flops_per_token(d, T, rows) == \
        pytest.approx(2.0 * (params + rows * 3 * D * 1024)
                      + 5 * H * 320 * T)
    assert mla_share_costs.flops_per_token(d, T, rows) == pytest.approx(
        6.0 * (params + rows * 3 * D * 1024) + 6.0 * 5 * H * 320 * T)


def test_costs_at_all_heads_read_as_the_issue_reckoned():
    """With all 32 heads: 759 346 190 trainable parameters (and 4 x 64
    selection-bias buffer entries: the issue's 759 346 446) and 950 MFLOP
    forward a token at T = 4096, latent attention 52 % of it."""
    d = {**_dims(), "heads": 32}
    assert mla_share_costs.parameters(d) == 759_346_190
    fwd = mla_share_costs.forward_flops_per_token(d, 4096, 2.0)
    assert fwd == pytest.approx(950e6, rel=2e-3)
    attention = 5 * (2 * mla_share_costs.latent_params(d) + 32 * 320 * 4096)
    assert attention / fwd == pytest.approx(0.52, abs=0.005)


def test_flash_cost_equals_the_accepted_one_at_equal_widths():
    for widths in (64, 128):
        mine = mla_share_costs.flash_attention(2, 16, 4096, widths, widths, 2)
        assert mine == kernel_costs.flash_attention(2, 16, 4096, widths, 2)
    cost = mla_share_costs.flash_attention(1, 32, 4096, 192, 128, 2)
    live = 32 * 4096 * 4096 / 2
    assert cost["fwd"]["flops"] == 2 * 320 * live
    assert cost["bwd"]["flops"] == 2 * (2 * 192 + 2 * 128) * live
    assert cost["fwd"]["bytes"] == 32 * 4096 * 2 * (2 * 192 + 2 * 128)
    assert cost["bwd"]["bytes"] == 32 * 4096 * 2 * (4 * 192 + 4 * 128)


def _trace(ops):
    return {"planes": {"devices": {0: {"ops": ops}}, "spans": []},
            "w0": 0.0, "w1": 1.0}


def _call(name):
    """A trace event's text: its HLO instruction, named after the kernel."""
    return (f'%{name}.1 = bf16[1,32,4096,128] custom-call(%q, %k, %v), '
            f'custom_call_target="tpu_custom_call"')


def test_flash_roofline_mla_counts_backward_calls_and_cannot_pass_100():
    read = _read("penroz_flash_roofline.mla")
    d = {**_dims(), "heads": 32}
    job = {"batch_size": 1, "block_size": 4096}
    least = mla_share_costs.flash_least_seconds(d, job, PEAKS)
    # five layers, forward and backward once each, run at exactly the
    # roofline: 100; a forward run again under recomputation lowers it
    names = ["penroz_flash_fwd"] * 5 + ["penroz_flash_bwd"] * 5
    split = least / 2
    ops = [(_call(n), i * split, (i + 1) * split) for i, n in
           enumerate(names)]
    art = {"kind": "train", "peaks": PEAKS, "dims": d, "job": job,
           "trace": {**_trace(ops), "w1": 100.0}}
    assert read(art) == pytest.approx(100.0)
    again = ops + [(_call("penroz_flash_fwd"), 50.0 + i * split,
                    50.0 + (i + 1) * split) for i in range(5)]
    art["trace"] = {**_trace(again), "w1": 100.0}
    assert read(art) == pytest.approx(100.0 * 10 / 15)
    # the least time is what no run can beat: each part is the larger of
    # FLOPs over the peak and bytes over the bandwidth
    cost = mla_share_costs.flash_attention(1, 32, 4096, 192, 128, 2)
    assert least == pytest.approx(sum(
        max(c["flops"] / PEAKS["flops_bf16"],
            c["bytes"] / PEAKS["hbm_bytes_per_s"]) for c in cost.values()))
    # a program whose dims name no value width, or no such kernel: nothing
    assert read({**art, "dims": {"d": 768, "heads": 12}}) is None
    art["trace"] = _trace([(_call("penroz_ce_fwd"), 0.0, 1e-3)])
    assert read(art) is None


def _span(name, t0, t1, **meta):
    return SimpleNamespace(name=name, t0=t0, t1=t1, meta=meta)


def test_peaks_and_plans_come_from_the_jobs_spans(monkeypatch):
    window = SimpleNamespace(t0=10.0, t1=20.0)
    spans = [
        _span("penroz/hc_plan", 1.0, 1.0, streams=4, sinkhorn_iters=20,
              sub_blocks=10, tokens=4096),
        _span("penroz/latent_plan", 1.0, 1.0, heads=32, path="expanded"),
        _span("penroz/train_epoch", 5.0, 6.0, hc_sinkhorn_err=0.5,
              moe_bias_absmax=0.3),                      # before the window
        _span("penroz/train_epoch", 11.0, 12.0, hc_sinkhorn_err=0.01,
              moe_bias_absmax=0.31),
        _span("penroz/train_epoch", 12.0, 13.0, hc_sinkhorn_err=0.03,
              moe_bias_absmax=0.30),
        _span("penroz/train_epoch", 19.5, 20.5, hc_sinkhorn_err=0.9)]
    monkeypatch.setattr(train_mla_share.program_spans, "spans",
                        lambda art: spans)
    art = {"window": window}
    assert train_mla_share.plan_of(art, "penroz/hc_plan")["sub_blocks"] == 10
    assert train_mla_share.plan_of(art, "penroz/latent_plan")["heads"] == 32
    assert train_mla_share.plan_of(art, "penroz/loop_plan") is None
    got = train_mla_share.peaks(art)
    assert got == {"hc_sinkhorn_err": 0.03, "moe_bias_absmax": 0.31,
                   "epochs": 2}
    read = _read("hc_sinkhorn_err")
    assert read({"peaks_counted": got}) == 0.03
    assert read({"peaks_counted": None}) is None
    assert read({}) is None
    # a program that counts none (the parent): nothing, and no error
    monkeypatch.setattr(train_mla_share.program_spans, "spans",
                        lambda art: [_span("penroz/train_epoch", 11.0, 12.0,
                                           moe_rows=5)])
    assert train_mla_share.peaks(art) is None
