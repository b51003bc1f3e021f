"""Kernels — ``ops/pallas/cross_entropy.py``: the two cross-entropy kernels'
share of their roofline in the traced training epochs, found by the names
the program gives them (``penroz_ce_fwd`` / ``penroz_ce_bwd``).  Least time
the chip could take (``lib/looped_costs.py::cross_entropy`` at micro-batch x
block rows of the whole vocabulary, bf16; bytes / peak bytes/s: both are
bandwidth-bound) times each kernel's calls, over the device time of every
event so named.  Under recomputation the forward kernel is called once more
a backward; each call reads the logits, so each counts.  A program that
names no such kernel gives nothing to read."""

from benchmark.lib import kernel_costs, looped_costs, trace_reduce


def read(art):
    trace = art.get("trace")
    if art.get("kind") != "train" or not trace or not art.get("peaks"):
        return None
    fwd, bwd = (trace_reduce.kernel_time(
        trace["planes"], trace["w0"], trace["w1"],
        {"name": f"penroz_ce_{part}", "result": ""})
        for part in ("fwd", "bwd"))
    if not fwd["calls"] or not bwd["calls"]:
        return None
    d, job = art["dims"], art["job"]
    cost = looped_costs.cross_entropy(
        job["batch_size"] * job["block_size"], d["vocab"], 2)
    least = sum(
        part["calls"] * kernel_costs.roofline_seconds(cost[name],
                                                      art["peaks"])[0]
        for name, part in (("fwd", fwd), ("bwd", bwd)))
    return 100.0 * least / (fwd["seconds"] + bwd["seconds"])
