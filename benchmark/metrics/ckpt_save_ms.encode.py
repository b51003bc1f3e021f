"""Data and checkpoints: the save's ``penroz/ckpt_encode`` child — the
header and a CRC32 over every array's bytes — median over the window's
periodic saves."""

from benchmark.lib import program_spans


def read(art):
    return program_spans.save_child_ms(art, "penroz/ckpt_encode")
