"""Device: share of the traced seconds (a few, inside the window) in which
no operation ran on the chip."""


def read(art):
    trace = art.get("trace")
    if art.get("kind") != "serve_open" or not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
