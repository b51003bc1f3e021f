"""Model runtime: host-clock time of one optimizer step (one epoch: all its
accumulated micro-steps, ending when the cost is on the host), median over
the window's steps that ran back to back between saves."""

from statistics import median

from benchmark.lib import cycles


def read(art):
    if art.get("kind") != "train":
        return None
    w = art["window"]
    steps = cycles.steady_steps([t for t, _ in art["epochs"]], art["saves"],
                                w.t0, w.t1)
    return 1000.0 * median(steps) if steps else None
