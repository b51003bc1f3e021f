"""Kernels — ``ops/pallas/flash_attention.py``: the flash kernels' share of
their roofline in the traced training epochs.  Least time the chip could
take (``lib/kernel_costs.py::flash_attention`` at the shapes the kernels
really get: micro-batch x heads x block x head size, bf16, causal; the
larger of FLOPs / peak and bytes / peak bytes/s, here compute) over the
device time of the kernels' events.  The program gives its kernels no names
yet, so the events are told by the name stack they carry (``jvp`` forward,
``transpose_jvp`` the two backward kernels) and their result shapes."""

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
