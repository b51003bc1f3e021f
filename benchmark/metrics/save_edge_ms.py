"""Data and checkpoints: host-clock time of a save cycle, outside its save,
that was no training at a steady step's pace.  For each whole cycle of the
window: its wall time less the time inside ``serialize`` less its steps x
the window's median back-to-back step (``train_step_ms``); the median over
the window's cycles (``lib/cycles.py::save_edge``).  It holds the
bookkeeping between a cycle's last step and its save (``penroz/train_stats``)
and whatever a step took beyond the median: on the chip, the step after the
first that follows a save, whose loader waits for the save's flush thread.
With ``train_step_ms`` it accounts for ``train_tokens_per_s``: tokens of a
step over (the median step + this, shared among a cycle's steps)."""

from statistics import median

from benchmark.lib import cycles


def read(art):
    if art.get("kind") != "train":
        return None
    w = art["window"]
    ends = [t for t, _ in art["epochs"]]
    steady = cycles.steady_steps(ends, art["saves"], w.t0, w.t1)
    if not steady:
        return None
    step = median(steady)
    return 1000.0 * median(
        cycles.save_edge(c, step)
        for c in cycles.anatomy(ends, art["saves"], w.t0, w.t1))
