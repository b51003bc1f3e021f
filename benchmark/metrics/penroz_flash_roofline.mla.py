"""Kernels — ``ops/pallas/flash_attention.py`` at unlike score and value
widths (latent attention: q, k 192 wide, v, o 128 wide): the flash kernels'
useful share of their roofline in the traced training epochs.  As
``penroz_flash_roofline.useful``: least time for one forward and one
backward (``lib/mla_share_costs.py::flash_attention`` at micro-batch x heads
x block, (D, Dv), bf16, causal: 2·(D + Dv) forward and 2·(2·D + 2·Dv)
backward a live score, every operand and result once) times the
**backward's** calls (one per layer and micro-step: ``penroz_flash_bwd``, or
its ``_dq`` half where the backward is split), over the device time of every
call named ``penroz_flash_*``; a forward run again under recomputation is
time spent and no work done.  A program that names no such kernel, or whose
dims name no value width, gives nothing to read."""

from benchmark.lib import mla_share_costs, trace_reduce


def read(art):
    trace, d = art.get("trace"), art.get("dims") or {}
    if (art.get("kind") != "train" or not trace or not art.get("peaks")
            or "d_v" not in d):
        return None
    timed = lambda name: trace_reduce.kernel_time(
        trace["planes"], trace["w0"], trace["w1"],
        {"name": name, "result": ""})
    every = timed("penroz_flash_")
    backward = timed(r"penroz_flash_bwd(?!_dkv|_delta)")
    if not every["calls"] or not backward["calls"]:
        return None
    least = backward["calls"] * mla_share_costs.flash_least_seconds(
        d, art["job"], art["peaks"])
    return 100.0 * least / every["seconds"]
