"""Data and checkpoints: the longest ``penroz/load_batch`` span inside the
window.  ``load_batch_ms`` is a median, which one slow loader never moves;
this is the loader of the step that waits, where the window holds one, and
what ``load_batch_ms.wait`` is a part of."""

from benchmark.lib import program_spans


def read(art):
    all_spans = program_spans.spans(art)
    if all_spans is None:
        return None
    found = program_spans.inside(all_spans, art["window"],
                                 "penroz/load_batch")
    return 1000.0 * max(s.t1 - s.t0 for s in found) if found else None
