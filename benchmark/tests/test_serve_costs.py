"""``serve_mfu_pct``'s FLOP count (``lib/serve_costs.py``) against a count by
hand at the rehearsal's widths, and the reader on a synthetic window."""

import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import serve_costs
from helpers import ROOT

CFG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "gpt2-large-hf.json")))
D, DEPTH, VOCAB = (CFG["rehearse"][k] for k in ("n_embd", "n_layer",
                                                "vocab_size"))


def test_rehearsal_widths_are_the_ones_counted_by_hand():
    assert (D, DEPTH, VOCAB) == (64, 2, 512)


def test_a_decode_step_by_hand():
    # a layer: QKV 64x192, projection 64x64, MLP 64x256 and 256x64
    layer = 64 * 192 + 64 * 64 + 64 * 256 + 256 * 64          # 49 152
    assert serve_costs.block_params(D, DEPTH) == 2 * layer == 98304
    # one query over 10 keys: QK^T 2*64*10 and PV 2*64*10 a layer
    attention = 2 * (2 * 64 * 10 + 2 * 64 * 10)                 # 5 120
    head = 2 * 64 * 512                                         # 65 536
    assert serve_costs.decode_flops(10, D, DEPTH, VOCAB) == \
        2 * 98304 + attention + head == 267264


def test_a_prefill_by_hand():
    # 5 prompt tokens: the blocks five times, 1+2+3+4+5 = 15 (query, key)
    # pairs a layer, the head once
    want = 2 * 98304 * 5 + 2 * (4 * 64 * 15) + 2 * 64 * 512
    assert serve_costs.prefill_flops(5, D, DEPTH, VOCAB) == want == 1056256
    # a prefill costs what its decode steps would, less the heads not needed
    steps = sum(serve_costs.decode_flops(c, D, DEPTH, VOCAB)
                for c in range(1, 6))
    assert steps - want == 4 * 2 * 64 * 512


def _request(prompt_len, token_at):
    return SimpleNamespace(prompt=[0] * prompt_len, token_at=token_at)


def test_a_window_counts_what_reached_the_client_inside_it():
    reqs = [_request(5, [1.0, 2.0, 3.0]),      # prefill, 2 decode steps
            _request(7, [0.5, 2.5, 9.0])]      # prefill out, 1 in, 1 out
    got = serve_costs.window_flops(reqs, 0.9, 3.5, D, DEPTH, VOCAB)
    want = (serve_costs.prefill_flops(5, D, DEPTH, VOCAB)
            + serve_costs.decode_flops(6, D, DEPTH, VOCAB)
            + serve_costs.decode_flops(7, D, DEPTH, VOCAB)
            + serve_costs.decode_flops(8, D, DEPTH, VOCAB))
    assert got == want


def _reader():
    path = os.path.join(ROOT, "benchmark", "metrics", "serve_mfu_pct.py")
    spec = importlib.util.spec_from_file_location("serve_mfu_pct", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_the_reader_divides_by_the_window_and_the_peak():
    reqs = [_request(5, [1.0, 2.0, 3.0])]
    art = {"kind": "serve_open", "peaks": {"flops_bf16": 1e9},
           "device": {"count": 1},
           "dims": {"d": D, "depth": DEPTH, "vocab": VOCAB},
           "window": {"requests": reqs, "t0": 0.0, "t1": 4.0}}
    flops = serve_costs.window_flops(reqs, 0.0, 4.0, D, DEPTH, VOCAB)
    assert _reader()(art) == pytest.approx(100.0 * flops / 4.0 / 1e9)
    # no peaks (a rehearsal), another kind, or nothing served: nothing
    assert _reader()({**art, "peaks": None}) is None
    assert _reader()({**art, "kind": "train"}) is None
    art["window"]["requests"] = []
    assert _reader()(art) is None
