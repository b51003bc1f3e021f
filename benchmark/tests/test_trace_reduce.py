"""The reduction from a trace to busy/idle share, time per operation, kernel
time and named idle gaps: on synthetic planes, and on a small recorded trace
(``data/train_slice.xplane.pb``: 90 ms around the boundary of two training
epochs of ``gpt2s-train-1chip`` on a v5e, my chip run, PR 24, cut out by
``tools/shrink_trace.py``)."""

import os

import pytest

from benchmark.lib import kernel_costs, peaks, trace_reduce as T

SLICE = os.path.join(os.path.dirname(__file__), "data",
                     "train_slice.xplane.pb")


def test_union_gaps_and_groups():
    assert T.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert T.gaps_of([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert T.gaps_of([(0, 10)], 2, 5) == []
    assert T.op_group("%fusion.2411 = f32[50304,768]{1,0} fusion(...)") \
        == "fusion"
    assert T.op_group("%transpose_jvp___.200 = bf16[1] custom-call(") \
        == "transpose_jvp___"
    assert T.op_group("copy-done.3.1") == "copy-done"


def test_nested_operations_count_once():
    # a while of 10 s holds two body operations of 3 s each
    events = [("%while.1 = x", 0.0, 10.0), ("%a.1 = x", 1.0, 4.0),
              ("%a.2 = x", 5.0, 8.0), ("%b = x", 11.0, 12.0)]
    selfs = {n: s for n, _, _, s in T.self_times(events)}
    assert selfs == {"%while.1 = x": 4.0, "%a.1 = x": 3.0, "%a.2 = x": 3.0,
                     "%b = x": 1.0}


def _planes():
    ops = [("%while.1 = x", 0.0, 4.0), ("%fusion.1 = x", 0.0, 2.0),
           ("%jvp__.7 = (bf16[2,2,8,4]{3}, f32[2,2,8,1]{3}) custom-call(q)",
            2.0, 4.0),
           ("%fusion.2 = x", 6.0, 7.0)]
    spans = [("penroz/train_epoch", 0.0, 4.5), ("penroz/load_batch", 4.6, 5.9),
             ("penroz/train_epoch", 5.9, 8.0)]
    return {"devices": {0: {"ops": ops}}, "spans": spans}


def test_reduce_synthetic():
    r = T.reduce_planes(_planes())
    assert r["window_s"] == 8.0 and r["busy_s"] == 5.0
    assert dict(map(tuple, r["device_ops"])) == {"fusion": 3.0, "jvp__": 2.0,
                                                "while": 0.0}
    # the 2 s gap's middle (5.0) lies in load_batch, the last second in
    # the second train_epoch span
    assert dict(map(tuple, r["idle_gaps"])) == {"penroz/load_batch": 2.0,
                                                "penroz/train_epoch": 1.0}
    k = T.kernel_time(r["planes"], r["w0"], r["w1"],
                      {"name": r"^%jvp_", "result": r"f32\[2,2,8,1\]"})
    assert k == {"seconds": 2.0, "calls": 1}
    cropped = T.reduce_planes(_planes(), crop_to_spans="penroz/load_batch")
    assert cropped["window_s"] == pytest.approx(1.3)
    assert cropped["busy_s"] == 0.0


def test_a_trace_with_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        T.reduce_planes({"devices": {}, "spans": []})
    with pytest.raises(ValueError):
        T.reduce_planes({"devices": {0: {"ops": []}},
                         "spans": []})


def test_recorded_slice():
    r = T.reduce(SLICE)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.090, abs=1e-6)
    # busy 78.7 ms of 90: the gap between two epoch programs is the idle
    assert r["busy_s"] == pytest.approx(0.078705, abs=1e-5)
    assert 0 < r["busy_s"] < r["window_s"]
    ops = dict(map(tuple, r["device_ops"]))
    assert max(ops, key=ops.get) == "fusion"
    # self times: the enclosing while is not counted again (its 90 ms would
    # double the sum); copies run beside compute, so a little over busy
    assert sum(ops.values()) < 1.05 * r["busy_s"]
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["penroz/train_epoch"] == pytest.approx(0.011291, abs=1e-5)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # the flash kernels, told by name stack and result shapes
    shape = r"bf16\[12,12,1024,64\]"
    fwd = T.kernel_time(r["planes"], r["w0"], r["w1"],
                        {"name": r"^%jvp_",
                         "result": shape + r".*f32\[12,12,1024,1\]"})
    bwd = T.kernel_time(r["planes"], r["w0"], r["w1"],
                        {"name": r"^%transpose_jvp_", "result": shape})
    assert (fwd["calls"], bwd["calls"]) == (12, 4)
    # one forward call: about 1.09 ms against a 0.098 ms compute roofline
    cost = kernel_costs.flash_attention(12, 12, 1024, 64, 2)
    least, bound = kernel_costs.roofline_seconds(
        cost["fwd"], peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute"
    assert 0.05 < least / (fwd["seconds"] / fwd["calls"]) < 0.15


def test_unknown_device_has_no_peaks():
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
