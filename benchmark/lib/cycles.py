"""Whole-cycle arithmetic for a training run that saves as it goes.

The program saves a full checkpoint, synchronously, whenever ten seconds
have passed since the last one, so its wall clock has a rhythm of its own
(about 10 s of training, then the save).  A window of fixed length cut
across that rhythm reads more or less training depending on where its edges
fall.  So the window is made of *whole cycles*: a cycle runs from the end of
one periodic save to the end of the next — the same event at both ends — and
the window is the cycles that complete within ``seconds`` of its opening.
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
