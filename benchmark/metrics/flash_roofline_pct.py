"""Kernels — ``ops/pallas/flash_attention.py``: the flash kernels' share of
their roofline in the traced training epochs.  Least time the chip could
take (``lib/kernel_costs.py::flash_attention`` at the shapes the kernels
really get: micro-batch x heads x block x head size, bf16, causal; the
larger of FLOPs / peak and bytes / peak bytes/s, here compute) over the
device time of the kernels' events, the events told by the name stack they
carry (``jvp`` forward, ``transpose_jvp`` the backward kernels) and their
head-major result shapes.

Retired in PR 37: no entry of ``BENCHMARK.json`` names it and no run loads
it.  Since PR 32 the cell's kernels work in ``(B, T, H·D)`` and it finds
nothing there; ``penroz_flash_roofline`` reads them by name in either
layout.  The file stays only because ``tests/test_tpu_compile.py`` loads it
to assert just that, and a ``benchmark`` PR may not touch ``tests/``: it
goes with that half of the test (PERF.md section 7)."""

from benchmark.lib import kernel_costs, trace_reduce


def read(art):
    trace = art.get("trace")
    if art.get("kind") != "train" or not trace or not art.get("peaks"):
        return None
    d, job = art["dims"], art["job"]
    head = d["d"] // d["heads"]
    shape = (rf"bf16\[{job['batch_size']},{d['heads']},"
             rf"{job['block_size']},{head}\]")
    lse = rf"f32\[{job['batch_size']},{d['heads']},{job['block_size']},1\]"
    fwd = trace_reduce.kernel_time(
        trace["planes"], trace["w0"], trace["w1"],
        {"name": r"^%jvp_", "result": shape + ".*" + lse})
    bwd = trace_reduce.kernel_time(
        trace["planes"], trace["w0"], trace["w1"],
        {"name": r"^%transpose_jvp_", "result": shape})
    if not fwd["calls"] or not bwd["calls"]:
        return None
    cost = kernel_costs.flash_attention(job["batch_size"], d["heads"],
                                        job["block_size"], head, 2)
    least = fwd["calls"] * (
        kernel_costs.roofline_seconds(cost["fwd"], art["peaks"])[0]
        + kernel_costs.roofline_seconds(cost["bwd"], art["peaks"])[0])
    return 100.0 * least / (fwd["seconds"] + bwd["seconds"])
