"""The readers of the program's own spans (``lib/program_spans.py`` and the
metric files on it), on a synthetic span list and window: cropping to the
window, saves cut by its edge left out whole, the medians over the same
saves, the error when the job trace's ring has lost the window's start,
and nothing at all from a program that records no such trace."""

import pytest

from benchmark.lib import program_spans as P
from benchmark.lib.cycles import Window
from benchmark.run import metric_reader

S = P.Span
READERS = ["train_epoch_ms", "load_batch_ms", "ckpt_save_ms",
           "ckpt_save_ms.d2h", "ckpt_save_ms.encode", "ckpt_save_ms.write",
           "ckpt_flush_ms", "train_recompile_ms"]


def _save(out, t0, parts, flush, periodic=True):
    """A save at ``t0`` whose d2h, encode and write last ``parts`` seconds
    and whose flush, begun at the save's end, lasts ``flush`` (``None``:
    still running)."""
    d2h, encode, write = parts
    t1 = t0 + d2h + encode + write + 0.01
    out.append(S("penroz/ckpt_save", t0, t1, None, {"periodic": periodic}))
    i = len(out) - 1
    out.append(S("penroz/ckpt_d2h", t0, t0 + d2h, i, {}))
    out.append(S("penroz/ckpt_encode", t0 + d2h, t0 + d2h + encode, i, {}))
    out.append(S("penroz/ckpt_write", t0 + d2h + encode, t1 - 0.01, i, {}))
    out.append(S("penroz/ckpt_flush", t1,
                 None if flush is None else t1 + flush, i, {}))
    return t1


def _epochs(out, t, n, batch=0.1, epoch=1.0):
    for _ in range(n):
        out.append(S("penroz/load_batch", t, t + batch, None, {}))
        out.append(S("penroz/train_epoch", t + batch, t + batch + epoch,
                     None, {}))
        out.append(S("penroz/train_wait", t + batch + 0.2,
                     t + batch + epoch, len(out) - 1, {}))
        t += batch + epoch
    return t


def synthetic():
    """Warm-up (a compile, a save that ends at the window's opening), two
    whole cycles (saves of 4 s and 6 s), then a third save cut by the
    window's close, and an untagged save inside the window."""
    out = []
    out.append(S("penroz/train_setup", 0.0, 5.0, None, {}))
    out.append(S("penroz/compile", 1.0, 4.0, 0, {"seconds": 3.0}))
    t = _epochs(out, 5.0, 3, epoch=2.0)            # warm-up epochs: slower
    t0 = _save(out, t, (1.0, 1.0, 1.0), 3.0)       # ends at the opening
    t = _epochs(out, t0, 3)
    t = _save(out, t, (1.0, 1.5, 1.5), 3.0)        # 4.01 s
    t = _epochs(out, t, 3, batch=0.3)
    out.append(S("penroz/compile", t - 0.5, t - 0.25, None,
                 {"seconds": 0.25}))
    t1 = _save(out, t, (1.0, 2.0, 3.0), None)      # 6.01 s, flush running
    _save(out, t1 + 0.5, (0.1, 0.1, 0.1), 0.1, periodic=False)
    t = _epochs(out, t1 + 1.0, 2, epoch=5.0)
    _save(out, t, (9.0, 9.0, 9.0), 9.0)            # begins inside, ends after
    return out, Window(t0, t + 5.0, 2, False)


def _art(spans, window):
    return {"kind": "train", "window": window, "program_spans": spans}


def test_readers_crop_to_the_window_and_take_medians():
    spans, window = synthetic()
    art = _art(spans, window)
    got = {name: metric_reader(name)(art) for name in READERS}
    # 3 + 3 + 2 epochs inside (the warm-up's 2 s epochs are out): median 1 s
    assert got["train_epoch_ms"] == pytest.approx(1000.0)
    # load_batch 0.1 x5 (3 + 2), 0.3 x3: median 0.1
    assert got["load_batch_ms"] == pytest.approx(100.0)
    # periodic saves whole inside: 4.01 and 6.01 s; the opening's save
    # (begun before), the untagged one and the one the close cuts are out
    assert got["ckpt_save_ms"] == pytest.approx(5010.0)
    assert got["ckpt_save_ms.d2h"] == pytest.approx(1000.0)
    assert got["ckpt_save_ms.encode"] == pytest.approx(1750.0)
    assert got["ckpt_save_ms.write"] == pytest.approx(2250.0)
    # the second save's flush never closed: the first's alone
    assert got["ckpt_flush_ms"] == pytest.approx(3000.0)
    # the set-up's compile is before the window, the later one inside
    assert got["train_recompile_ms"] == pytest.approx(250.0)


def test_steady_state_reads_zero_recompile_and_no_save_reads_nothing():
    spans, window = synthetic()
    quiet = [s for s in spans if s.name != "penroz/compile"
             and "ckpt" not in s.name]
    art = _art(quiet, window)
    assert metric_reader("train_recompile_ms")(art) == 0.0
    assert metric_reader("ckpt_save_ms")(art) is None
    assert metric_reader("ckpt_save_ms.write")(art) is None
    assert metric_reader("ckpt_flush_ms")(art) is None
    assert metric_reader("train_epoch_ms")(art) == pytest.approx(1000.0)


@pytest.fixture
def tracing():
    from penroz_tpu.utils import tracing
    tracing.reset()
    yield tracing
    tracing.reset()


def _record(trace, name, t0, t1, parent=None, **meta):
    sp = trace.span(name, t0=t0, parent=parent, **meta)
    trace.end(sp, t1=t1)
    return sp


def test_finds_the_training_trace_and_flattens_it(tracing):
    tracing.maybe_trace("other", route="/generate/", model_id=P.MODEL)
    tracing.maybe_trace("old", job=True, route="/train/", model_id=P.MODEL)
    tracing.maybe_trace("else", job=True, route="/train/", model_id="m2")
    trace = tracing.maybe_trace("new", job=True, route="/train/",
                                model_id=P.MODEL)
    save = _record(trace, "penroz/ckpt_save", 10.0, 14.0, periodic=True)
    _record(trace, "penroz/ckpt_write", 12.0, 14.0, parent=save, bytes=5)
    _record(trace, "penroz/train_epoch", 14.0, 15.0, epoch=1)
    open_flush = trace.span("penroz/ckpt_flush", t0=14.0, parent=save)
    assert open_flush.t1 is None
    trace.finish("error")       # the registry outlives the job
    assert P.find_trace() is trace
    art = {"kind": "train", "window": Window(9.0, 16.0, 1, False)}
    assert P.spans(art) == [
        S("penroz/ckpt_save", 10.0, 14.0, None, {"periodic": True}),
        S("penroz/ckpt_write", 12.0, 14.0, 0, {"bytes": 5}),
        S("penroz/ckpt_flush", 14.0, None, 0, {}),
        S("penroz/train_epoch", 14.0, 15.0, None, {"epoch": 1})]
    assert metric_reader("ckpt_save_ms.write")(art) == pytest.approx(2000.0)
    assert metric_reader("ckpt_flush_ms")(art) is None


def test_a_ring_that_lost_the_windows_start_is_an_error(tracing,
                                                        monkeypatch):
    monkeypatch.setattr(tracing, "JOB_HEAD", 1)
    monkeypatch.setattr(tracing, "JOB_RING", 4)
    trace = tracing.maybe_trace("j", job=True, route="/train/",
                                model_id=P.MODEL)
    _record(trace, "penroz/train_setup", 0.0, 1.0)
    for i in range(1, 9):       # epochs at 1..8 s; the ring keeps 5..8
        _record(trace, "penroz/train_epoch", float(i), i + 0.9)
    assert trace.dropped_spans == 4
    held = {"kind": "train", "window": Window(5.0, 9.0, 1, False)}
    assert metric_reader("train_epoch_ms")(held) == pytest.approx(900.0)
    lost = {"kind": "train", "window": Window(4.5, 9.0, 1, False)}
    with pytest.raises(RuntimeError, match="lost the window's start"):
        metric_reader("train_epoch_ms")(lost)
    # a ring that never dropped anything holds whatever there was
    fresh = tracing.maybe_trace("k", job=True, route="/train/",
                                model_id=P.MODEL)
    _record(fresh, "penroz/train_epoch", 100.0, 101.0)
    assert metric_reader("train_epoch_ms")(
        {"kind": "train", "window": Window(50.0, 102.0, 1, False)}
    ) == pytest.approx(1000.0)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_trace_reads_nothing(tracing, name):
    """The parent of the PR that added the readers: no ``/train/`` trace in
    the registry.  Nothing is raised and the line leaves the metric out."""
    window = Window(0.0, 10.0, 1, False)
    assert metric_reader(name)({"kind": "train", "window": window}) is None
    assert metric_reader(name)({"kind": "serve_open"}) is None
