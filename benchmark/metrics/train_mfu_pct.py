"""Model runtime: model FLOP/s utilisation *while training* — (6N + 12·L·d·T)
FLOPs per token x tokens per second over the window's back-to-back steps
(saves left out: they are ``ckpt_stall_pct``'s), over the chip's published
bf16 peak.  N counts matmul parameters only; recomputation counts nothing."""

from statistics import median

from benchmark.lib import cycles


def read(art):
    if art.get("kind") != "train" or not art.get("peaks"):
        return None
    w = art["window"]
    steps = cycles.steady_steps([t for t, _ in art["epochs"]], art["saves"],
                                w.t0, w.t1)
    if not steps:
        return None
    tokens_per_step = art["epochs"][0][1]
    rate = tokens_per_step / median(steps)
    chips = art["device"]["count"]
    return 100.0 * art["flops_per_token"] * rate / (
        chips * art["peaks"]["flops_bf16"])
