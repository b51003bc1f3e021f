"""Data and checkpoints: the loader's part of a step — the program's
``penroz/load_batch`` span (the micro-batches of one optimizer step read
and stacked on the host) — median over the window."""

from benchmark.lib import program_spans


def read(art):
    return program_spans.span_ms(art, "penroz/load_batch")
