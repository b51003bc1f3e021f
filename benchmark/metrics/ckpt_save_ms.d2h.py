"""Data and checkpoints: the save's ``penroz/ckpt_d2h`` child — every array
materialised on the host (``np.asarray``; one span for the loop) — median
over the window's periodic saves."""

from benchmark.lib import program_spans


def read(art):
    return program_spans.save_child_ms(art, "penroz/ckpt_d2h")
