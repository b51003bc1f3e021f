"""Data and checkpoints: the background copy of a saved checkpoint from
shared memory to ``models/`` — the ``penroz/ckpt_flush`` span of the flush
thread, a child of the save that spawned it — median over the window's
periodic saves.  Training runs on beside it."""

from benchmark.lib import program_spans


def read(art):
    return program_spans.save_child_ms(art, "penroz/ckpt_flush")
