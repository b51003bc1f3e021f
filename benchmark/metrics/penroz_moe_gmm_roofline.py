"""Kernels — ``ops/pallas/moe_gmm.py``: the grouped products' share of their
roofline in the traced training epochs, found by the names the program gives
them (``penroz_moe_gmm_fwd`` / ``_bwd_dx`` / ``_bwd_dw``).  Least time the
chip could take for the rows **really routed**
(``lib/moe_share_costs.py::grouped_least_seconds``: the larger of FLOPs over
the peak and bytes over the bandwidth, in each of the three phases), over the
device time of every custom call so named.  The rows are the program's own
count for the very epochs the trace holds whole: ``moe_rows`` of their
``penroz/train_epoch`` spans (``kinds/train_moe_share.py::traced_routing``
finds them).  Padding rows, a round's empty tiles and the routed path's
recomputation in the backward are time spent and count nothing.  A program
that names no such kernel, or counts no rows, gives nothing to read."""

from benchmark.lib import moe_share_costs, trace_reduce


def read(art):
    trace, moe = art.get("trace"), art.get("moe_traced")
    if (art.get("kind") != "train" or not trace or not art.get("peaks")
            or not moe or not moe.get("moe_rows")):
        return None
    planes, w0, w1 = trace["planes"], trace["w0"], trace["w1"]
    every = trace_reduce.kernel_time(
        planes, w0, w1, {"name": "penroz_moe_gmm_", "result": ""})
    if not every["calls"]:
        return None
    d = art["dims"]
    sparse = sum(kind == "sparse" for kind in d["mlp_types"])
    least = moe_share_costs.grouped_least_seconds(
        moe["moe_rows"],
        moe["epochs"] * sparse * art["micro_steps_per_epoch"], d,
        art["peaks"])
    return 100.0 * least / every["seconds"]
