"""Device: share of the traced stretch in which no operation ran on the chip.
The stretch is a few steady *training epochs* between two saves (cropped to
whole ``penroz/train_epoch`` spans), not the save cycle: the stall of the
cycle is ``ckpt_stall_pct``."""


def read(art):
    trace = art.get("trace")
    if art.get("kind") != "train" or not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
