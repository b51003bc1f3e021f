"""Data and checkpoints: one synchronous save as the program times it — its
``penroz/ckpt_save`` span inside ``serialize`` — median over the periodic
saves inside the window.  The inside twin of ``ckpt_stall_pct``."""

from benchmark.lib import program_spans


def read(art):
    all_spans = program_spans.spans(art)
    if all_spans is None:
        return None
    saves = program_spans.periodic_saves(all_spans, art["window"])
    return program_spans.median_ms([all_spans[i] for i in saves])
