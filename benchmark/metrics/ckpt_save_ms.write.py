"""Data and checkpoints: the save's ``penroz/ckpt_write`` child — header and
array stream written to the shared-memory file, and its rename — median
over the window's periodic saves."""

from benchmark.lib import program_spans


def read(art):
    return program_spans.save_child_ms(art, "penroz/ckpt_write")
