"""Kernels — ``ops/pallas/flash_attention.py``: the flash kernels' share of
their roofline in the traced training epochs, with the kernels found by the
names the program gives them (``pallas_call(name=)``: ``penroz_flash_fwd``;
``penroz_flash_bwd``, ``penroz_flash_bwd_dq`` / ``_dkv``, ``penroz_flash_
bwd_delta``), whatever layout their operands and results have.  Least time
the chip could take (``lib/kernel_costs.py::flash_attention`` at the shapes
the kernels really get: micro-batch x heads x block x head size, bf16,
causal; the larger of FLOPs / peak and bytes / peak bytes/s, here compute;
the backward's bytes include O and dO, so the δ kernel's time belongs to
it) over the device time of every event so named.  A program that names no
such kernel (before PR 32) gives nothing to read."""

from benchmark.lib import kernel_costs, trace_reduce


def read(art):
    trace = art.get("trace")
    if art.get("kind") != "train" or not trace or not art.get("peaks"):
        return None
    fwd, bwd = (trace_reduce.kernel_time(
        trace["planes"], trace["w0"], trace["w1"],
        {"name": f"penroz_flash_{part}", "result": ""})
        for part in ("fwd", "bwd"))
    if not fwd["calls"] or not bwd["calls"]:
        return None
    d, job = art["dims"], art["job"]
    cost = kernel_costs.flash_attention(job["batch_size"], d["heads"],
                                        job["block_size"],
                                        d["d"] // d["heads"], 2)
    least = fwd["calls"] * (
        kernel_costs.roofline_seconds(cost["fwd"], art["peaks"])[0]
        + kernel_costs.roofline_seconds(cost["bwd"], art["peaks"])[0])
    return 100.0 * least / (fwd["seconds"] + bwd["seconds"])
