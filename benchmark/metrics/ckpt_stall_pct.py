"""Data and checkpoints: share of the window's whole save cycles spent
inside the program's synchronous ``serialize`` (wrapper timestamps)."""

from benchmark.lib import cycles


def read(art):
    if art.get("kind") != "train":
        return None
    w = art["window"]
    return 100.0 * cycles.stall_seconds(art["saves"], w.t0, w.t1) / (
        w.t1 - w.t0)
