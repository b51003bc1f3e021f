"""Model runtime: time the job spent compiling inside the window — the sum
of its ``penroz/compile`` spans (one per backend compile, recorded by the
program's ``jax.monitoring`` listener).  Steady state: 0."""

from benchmark.lib import program_spans


def read(art):
    all_spans = program_spans.spans(art)
    if all_spans is None:
        return None
    return sum(1000.0 * s.meta["seconds"] for s in program_spans.inside(
        all_spans, art["window"], "penroz/compile"))
