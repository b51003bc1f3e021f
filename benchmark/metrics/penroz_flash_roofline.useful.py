"""Kernels — ``ops/pallas/flash_attention.py`` under recomputation: the
flash kernels' *useful* share of their roofline in the traced training
epochs.  Where every block application is recomputed in the backward (a
looped stack), the forward kernel runs twice a backward, and the second run
is time spent and no work done.  So: least time for one forward and one
backward (``lib/kernel_costs.py::flash_attention`` at micro-batch x heads x
block x head size, bf16, causal) times the **backward's** calls (one per
layer application and micro-step: ``penroz_flash_bwd``, or its ``_dq`` half
where the backward is split), over the device time of every call named
``penroz_flash_*``.  ``penroz_flash_roofline`` multiplies the forward's calls
instead and would read too high here.  A program that names no such kernel
gives nothing to read."""

from benchmark.lib import kernel_costs, trace_reduce


def read(art):
    trace = art.get("trace")
    if art.get("kind") != "train" or not trace or not art.get("peaks"):
        return None
    timed = lambda name: trace_reduce.kernel_time(
        trace["planes"], trace["w0"], trace["w1"],
        {"name": name, "result": ""})
    every = timed("penroz_flash_")
    backward = timed(r"penroz_flash_bwd(?!_dkv|_delta)")
    if not every["calls"] or not backward["calls"]:
        return None
    d, job = art["dims"], art["job"]
    head_dim = d.get("head_dim") or d["d"] // d["heads"]
    cost = kernel_costs.flash_attention(job["batch_size"], d["heads"],
                                        job["block_size"], head_dim, 2)
    least = backward["calls"] * sum(
        kernel_costs.roofline_seconds(cost[part], art["peaks"])[0]
        for part in ("fwd", "bwd"))
    return 100.0 * least / every["seconds"]
