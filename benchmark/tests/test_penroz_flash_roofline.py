"""``penroz_flash_roofline``: the reader finds the flash kernels by name at
any result shape, counts the δ kernel with the backward, and reads nothing
from a program that names none."""

import importlib.util
import os

import pytest

from benchmark.lib import kernel_costs, peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def _read():
    path = os.path.join(HERE, "..", "metrics", "penroz_flash_roofline.py")
    spec = importlib.util.spec_from_file_location("penroz_flash_roofline",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _art(ops):
    return {"kind": "train", "peaks": peaks.peaks_for("TPU v5 lite"),
            "dims": {"d": 768, "heads": 12},
            "job": {"batch_size": 12, "block_size": 1024},
            "trace": {"planes": {"devices": {0: {"ops": ops}}, "spans": []},
                      "w0": 0.0, "w1": 1.0}}


NAMED = [
    ("%jvp_penroz_flash_fwd_.3 = (bf16[12,1024,768]{2,1,0}, "
     "f32[12,6,2,1024]{3,2,1,0}) custom-call(a, b)", 0.000, 0.001),
    ("%transpose_jvp_penroz_flash_bwd_delta__.5 = f32[12,6,2,1024]{3,2,1,0} "
     "custom-call(a, b)", 0.010, 0.0105),
    ("%transpose_jvp_penroz_flash_bwd__.9 = (bf16[12,1024,768]{2,1,0}, "
     "bf16[12,1024,768]{2,1,0}, bf16[12,1024,768]{2,1,0}) custom-call(a)",
     0.020, 0.0215),
    # the cross-entropy kernels keep the bare names and are not counted
    ("%jvp__.7 = f32[12288]{0} custom-call(a)", 0.030, 0.040),
    ("%fusion.1 = bf16[12,1024,768]{2,1,0} fusion(a)", 0.050, 0.060),
]


def test_reads_named_kernels_at_any_shape():
    cost = kernel_costs.flash_attention(12, 12, 1024, 64, 2)
    pk = peaks.peaks_for("TPU v5 lite")
    least = sum(kernel_costs.roofline_seconds(cost[k], pk)[0]
                for k in ("fwd", "bwd"))
    assert _read()(_art(NAMED)) == pytest.approx(100.0 * least / 0.003)
    # the (B, H, T, D) entry's calls carry the same names
    bhtd = [(n.replace("bf16[12,1024,768]", "bf16[12,12,1024,64]"), a, b)
            for n, a, b in NAMED]
    assert _read()(_art(bhtd)) == pytest.approx(100.0 * least / 0.003)


def test_unnamed_program_reads_nothing():
    parent = [("%jvp__.7 = (bf16[12,12,1024,64]{3,2,1,0}, "
               "f32[12,12,1024,1]{3,2,1,0}) custom-call(a)", 0.0, 0.001),
              ("%transpose_jvp___.9 = bf16[12,12,1024,64]{3,2,1,0} "
               "custom-call(a)", 0.01, 0.012)]
    assert _read()(_art(parent)) is None
    assert _read()({"kind": "train", "trace": None}) is None
