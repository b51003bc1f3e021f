"""What the host thread did inside the training job's spans, for the readers
that say whether a span *worked* or *waited* (``load_batch_ms.wait``,
``ckpt_save_ms.sys``, ...).

The program puts the calling thread's own account on every span of a job
trace (``penroz_tpu/utils/tracing.py``: ``host`` = cpu_ms, sys_ms,
major_faults, minor_faults, waits, preempted, beside ``meta``) and the
loader's on ``penroz/load_batch`` (counters ``scan_ms``, ``gather_ms``).
``lib/program_spans.py`` flattens the same trace without ``host``; this walk
keeps it: ``Span(name, t0, t1, parent, meta, host)`` on ``time.monotonic()``,
``host`` ``None`` where the program recorded none.

A *cycle* is a whole save cycle of the window as ``lib/cycles.py`` cuts it
(the end of one periodic save to the end of the next).  A per-cycle metric
is the median over the window's cycles of the sum over a cycle's spans, so
that a thing that happens once a cycle (the step that waits) is not
averaged away over the cycle's steps; a span cut by a cycle's edge is left
out whole.

A program without the field (the parent of the PR that added this file)
gives ``None`` and the readers leave their metrics out.
"""

from __future__ import annotations

from statistics import median
from typing import NamedTuple

from benchmark.lib import cycles, program_spans

LOAD = "penroz/load_batch"
SAVE_PASSES = ("penroz/ckpt_encode", "penroz/ckpt_write")


class Span(NamedTuple):
    name: str
    t0: float
    t1: float | None
    parent: int | None
    meta: dict
    host: dict | None


def flatten(root) -> list[Span]:
    """``program_spans.flatten`` with the thread's account kept."""
    out: list[Span] = []

    def visit(node, parent):
        for child in list(node.children):
            host = getattr(child, "host", None)
            out.append(Span(child.name, child.t0, child.t1, parent,
                            dict(child.meta),
                            dict(host) if host is not None else None))
            visit(child, len(out) - 1)

    visit(root, None)
    return out


def spans(art) -> list[Span] | None:
    """The job's spans for a training run's ``art``; ``None`` where the
    program recorded none.  Flattened once per run."""
    if art.get("kind") != "train":
        return None
    if "host_spans" not in art:
        trace = program_spans.find_trace()
        if trace is not None:
            program_spans.check_ring(trace, art["window"])
        art["host_spans"] = (flatten(trace.root) if trace is not None
                             else None)
    return art["host_spans"]


def cycle_bounds(art) -> list[tuple[float, float]]:
    """(start, end) of the window's whole cycles."""
    w = art["window"]
    return [(c.t0, c.t1) for c in cycles.anatomy(
        [t for t, _ in art["epochs"]], art["saves"], w.t0, w.t1)]


def waited_ms(s: Span) -> float:
    """What the thread did not run of a closed span that has its account."""
    return 1000.0 * (s.t1 - s.t0) - s.host["cpu_ms"]


def per_cycle(art, name: str, value) -> float | None:
    """Median over the window's cycles of the sum of ``value(span)`` over
    the cycle's whole spans ``name``.  ``value`` returns ``None`` for a
    span that lacks what it reads: the metric is then left out."""
    all_spans = spans(art)
    if all_spans is None:
        return None
    sums = []
    for t0, t1 in cycle_bounds(art):
        values = [value(s) for s in all_spans if s.name == name
                  and s.t1 is not None and t0 <= s.t0 and s.t1 <= t1]
        if any(v is None for v in values):
            return None
        sums.append(sum(values))
    return median(sums) if sums else None


def periodic_saves(art, children: tuple[str, ...], value) -> float | None:
    """Median over the window's periodic saves of the sum of
    ``value(span)`` over the save's closed children named in ``children``
    (a flush may outlive the run: an open one leaves its save out)."""
    all_spans = spans(art)
    if all_spans is None:
        return None
    sums = []
    for i in program_spans.periodic_saves(all_spans, art["window"]):
        kids = [s for s in all_spans if s.parent == i and s.name in children]
        if len(kids) < len(children) or any(s.t1 is None for s in kids):
            continue
        if any(s.host is None for s in kids):
            return None
        sums.append(sum(value(s) for s in kids))
    return median(sums) if sums else None
