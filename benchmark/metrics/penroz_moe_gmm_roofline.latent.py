"""Kernels — ``ops/pallas/moe_gmm.py`` under experts that are not gated and
live in a latent: the grouped products' share of their roofline in the traced
training epochs, as ``penroz_moe_gmm_roofline`` reckons its three products
but for **two** (up, down) at the latent's width
(``lib/ssm_share_costs.py::grouped_least_seconds``: the larger of FLOPs over
the peak and bytes over the bandwidth, in each of the three phases), for the
rows really routed in the very epochs the trace holds whole (``moe_rows`` of
their ``penroz/train_epoch`` spans), over the device time of every custom
call named ``penroz_moe_gmm_*``.  Padding rows, empty tiles and the
recomputed forwards are time spent and count nothing.  A program that names
no such kernel, or counts no rows, gives nothing to read."""

from benchmark.lib import ssm_share_costs, trace_reduce


def read(art):
    trace, moe = art.get("trace"), art.get("moe_traced")
    d = art.get("dims") or {}
    if (art.get("kind") != "train" or not trace or not art.get("peaks")
            or not moe or not moe.get("moe_rows") or "latent" not in d):
        return None
    every = trace_reduce.kernel_time(
        trace["planes"], trace["w0"], trace["w1"],
        {"name": "penroz_moe_gmm_", "result": ""})
    if not every["calls"]:
        return None
    least = ssm_share_costs.grouped_least_seconds(
        moe["moe_rows"],
        moe["epochs"] * d["pattern"].count("E")
        * art["micro_steps_per_epoch"], d, art["peaks"])
    return 100.0 * least / every["seconds"]
