"""Whole-cycle arithmetic for a training run that saves as it goes.

The program saves a full checkpoint, synchronously, whenever ten seconds
have passed since the last one, so its wall clock has a rhythm of its own
(about 10 s of training, then the save).  A window of fixed length cut
across that rhythm reads more or less training depending on where its edges
fall.  So the window is made of *whole cycles*: a cycle runs from the end of
one periodic save to the end of the next — the same event at both ends — and
the window is the cycles that complete within ``seconds`` of its opening.

A cycle's wall time has three parts (:func:`anatomy`): the time inside its
save (:func:`stall_seconds`, ``ckpt_stall_pct``); its optimizer steps at the
pace of a *steady* one, the median of those that ran back to back
(:func:`steady_steps`, ``train_step_ms``); and the rest, the save's *edge*
(:func:`save_edge`, ``save_edge_ms``): whatever its steps took beyond that
pace, the first after the save measured from the save's end, plus the
bookkeeping between the last step's end and the save's start.
``train_tokens_per_s`` is taken over the last two together: every token of
the whole cycles over all their time outside the saves.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Window:
    t0: float           # opening: the end of the warm-up's periodic save
    t1: float           # close: the end of the last whole cycle
    cycles: int
    overran: bool       # no cycle completed in time: went on to the first


def whole_cycles(save_ends: list[float], seconds: float) -> Window | None:
    """The window over ``save_ends`` (ends of periodic saves, ascending).

    It opens at the first of them (everything before is warm-up) and closes
    at the last one that lies within ``seconds`` of the opening.  Where none
    does, it runs on to the end of the first cycle and says so.  ``None``
    while no cycle has completed at all."""
    if len(save_ends) < 2:
        return None
    t0 = save_ends[0]
    inside = [t for t in save_ends[1:] if t - t0 <= seconds]
    if inside:
        return Window(t0, inside[-1], len(inside), False)
    return Window(t0, save_ends[1], 1, True)


def closed(save_ends: list[float], seconds: float, now: float) -> bool:
    """Whether nothing that can still happen changes :func:`whole_cycles`:
    the time is up and at least one cycle has completed."""
    return (len(save_ends) >= 2 and now - save_ends[0] > seconds)


def tokens_in(epochs: list[tuple[float, int]], t0: float, t1: float) -> int:
    """Tokens of the epochs that ended in ``(t0, t1]``; ``epochs`` holds
    (end time, tokens).  An epoch belongs to the cycle its end falls in:
    saves happen between epochs, so no epoch straddles a delimiter."""
    return sum(n for t, n in epochs if t0 < t <= t1)


def stall_seconds(saves: list[tuple[float, float]], t0: float,
                  t1: float) -> float:
    """Seconds spent inside saves (start, end) that ended in ``(t0, t1]``."""
    return sum(b - a for a, b in saves if t0 < b <= t1)


def steady_steps(epoch_ends: list[float], saves: list[tuple[float, float]],
                 t0: float, t1: float) -> list[float]:
    """Durations of the optimizer steps inside ``(t0, t1]`` that ran back to
    back: end-to-end gaps between consecutive epochs with no save starting
    between them (the first epoch after a save is measured from the save's
    end by nobody: it has no predecessor on the same footing)."""
    out = []
    for a, b in zip(epoch_ends, epoch_ends[1:]):
        if not (t0 < a and b <= t1):
            continue
        if any(a <= s < b for s, _ in saves):
            continue
        out.append(b - a)
    return out


@dataclass(frozen=True)
class Cycle:
    t0: float                   # the end of the save that opens it
    t1: float                   # the end of the save that closes it
    steps: tuple[float, ...]    # seconds each; the first is taken from t0
    before_save: float          # the last step's end to its save's start
    stall: float                # inside its save


def anatomy(epoch_ends: list[float], saves: list[tuple[float, float]],
            t0: float, t1: float) -> list[Cycle]:
    """The whole cycles inside ``[t0, t1]``, part by part; ``saves`` are the
    periodic ones (start, end), ascending, and both ``t0`` and ``t1`` are
    ends of saves (:func:`whole_cycles`).  The parts add up to the cycle's
    wall time."""
    out = []
    closing = [(a, b) for a, b in saves if t0 < b <= t1]
    for (a, b), opened in zip(closing, [t0] + [b for _, b in closing]):
        ends = [t for t in epoch_ends if opened < t <= b]
        out.append(Cycle(
            t0=opened, t1=b,
            steps=tuple(y - x for x, y in zip([opened] + ends, ends)),
            before_save=a - (ends[-1] if ends else opened), stall=b - a))
    return out


def save_edge(cycle: Cycle, step: float) -> float:
    """Seconds of ``cycle`` outside its save that were no training at a
    steady step's pace (``step``, seconds): its wall time less the time
    inside its save less its steps x ``step``.  That is what its steps took
    beyond ``step`` each (mostly the step that waits for the flush thread of
    the save before it: PERF.md section 5) plus the bookkeeping before the
    save."""
    return sum(cycle.steps) - len(cycle.steps) * step + cycle.before_save
