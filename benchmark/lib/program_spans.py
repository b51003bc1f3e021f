"""The program's own spans of the training job, for the readers that time
its layers from inside (``train_epoch_ms``, ``ckpt_save_ms`` and its
children, ...).

The service runs in this process, so the trace that ``PUT /train/`` started
for model ``bench`` (``penroz_tpu/utils/tracing.py``: a job trace) is in that
module's registry of live and completed traces, which outlives the model's
deletion and the server's stop.  It is flattened here to
``Span(name, t0, t1, parent, meta)`` on ``time.monotonic()`` — the clock
``art["window"]`` is on — with ``parent`` an index into the same list
(``None``: a top-level span of the job).  A span still open has ``t1``
``None``.  A job trace keeps a ring of its newest top-level spans; where
the ring has already let go of the window's start the readers would time
half a window, so that is an error and not a number.

A program without such a trace (the parent of the PR that added this file)
gives ``None`` and the readers leave their metrics out.
"""

from __future__ import annotations

from statistics import median
from typing import NamedTuple

MODEL = "bench"
SAVE = "penroz/ckpt_save"


class Span(NamedTuple):
    name: str
    t0: float
    t1: float | None
    parent: int | None
    meta: dict


def find_trace(model_id: str = MODEL):
    """The newest ``/train/`` job trace of ``model_id``, or ``None``."""
    from penroz_tpu.utils import tracing
    found = [t for t in tracing.live() + tracing.completed(limit=10**6)
             if t.meta.get("route") == "/train/"
             and t.meta.get("model_id") == model_id]
    return max(found, key=lambda t: t.t0, default=None)


def flatten(root) -> list[Span]:
    """The tree under ``root`` (not ``root`` itself), parents before their
    children, siblings in the order they were opened."""
    out: list[Span] = []

    def visit(node, parent):
        for child in list(node.children):
            out.append(Span(child.name, child.t0, child.t1, parent,
                            dict(child.meta)))
            visit(child, len(out) - 1)

    visit(root, None)
    return out


def check_ring(trace, window) -> None:
    """Raise if the job trace no longer holds the window's start."""
    from penroz_tpu.utils import tracing
    kids = list(trace.root.children)
    if not trace.dropped_spans or len(kids) <= tracing.JOB_HEAD:
        return
    oldest = kids[tracing.JOB_HEAD].t0
    if oldest > window.t0:
        raise RuntimeError(
            f"the training trace's ring has lost the window's start: its "
            f"oldest kept span opened {oldest - window.t0:.3f} s after the "
            f"window did ({trace.dropped_spans} spans dropped); the "
            f"program's ring (tracing.JOB_RING) is too short for this cell")


def spans(art) -> list[Span] | None:
    """The job's spans for a training run's ``art``; ``None`` where the
    program recorded none.  Flattened once per run."""
    if art.get("kind") != "train":
        return None
    if "program_spans" not in art:
        trace = find_trace()
        if trace is not None:
            check_ring(trace, art["window"])
        art["program_spans"] = (flatten(trace.root) if trace is not None
                                else None)
    return art["program_spans"]


def whole(s: Span, window) -> bool:
    """Closed, and begun and ended within the window: a span cut by the
    window's edge is left out whole."""
    return s.t1 is not None and window.t0 <= s.t0 and s.t1 <= window.t1


def inside(all_spans: list[Span], window, name: str) -> list[Span]:
    return [s for s in all_spans if s.name == name and whole(s, window)]


def median_ms(found: list[Span]) -> float | None:
    return 1000.0 * median(s.t1 - s.t0 for s in found) if found else None


def span_ms(art, name: str) -> float | None:
    """Median duration, in ms, of the spans ``name`` inside the window."""
    all_spans = spans(art)
    if all_spans is None:
        return None
    return median_ms(inside(all_spans, art["window"], name))


def periodic_saves(all_spans: list[Span], window) -> list[int]:
    """Indices of the periodic ``penroz/ckpt_save`` spans inside the
    window: the saves that end its whole cycles."""
    return [i for i, s in enumerate(all_spans)
            if s.name == SAVE and s.meta.get("periodic")
            and whole(s, window)]


def save_child_ms(art, child: str) -> float | None:
    """Median duration, in ms, of the child ``child`` over the window's
    periodic saves (closed children only: a flush may outlive the run)."""
    all_spans = spans(art)
    if all_spans is None:
        return None
    saves = set(periodic_saves(all_spans, art["window"]))
    return median_ms([s for s in all_spans
                      if s.name == child and s.parent in saves
                      and s.t1 is not None])
