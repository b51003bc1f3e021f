"""The readers of the host thread's account (``lib/host_account.py`` and the
nine metric files on it), on synthetic spans and a synthetic window: sums
over a whole save cycle and their median over the window's cycles, a span
cut by the window left out whole, an open flush left out, and nothing at
all from a program whose spans carry no ``host``."""

import pytest

from benchmark.lib import host_account as H
from benchmark.lib import program_spans as P
from benchmark.lib.cycles import Window
from benchmark.run import metric_reader

READERS = ["load_batch_ms.max", "load_batch_ms.wait", "load_batch_ms.scan",
           "load_batch_ms.gather", "load_batch_major_faults",
           "save_edge_ms.train_epoch", "ckpt_save_ms.wait",
           "ckpt_save_ms.sys", "ckpt_flush_ms.cpu"]
# the two that read spans a program without the field records too
OUTSIDE = ["load_batch_ms.max", "save_edge_ms.train_epoch"]


def _host(cpu, sys=0.0, major=0):
    return {"cpu_ms": cpu, "sys_ms": sys, "major_faults": major,
            "minor_faults": 0, "waits": 0, "preempted": 0}


class Job:
    """Spans of a job and the spy's view of it, built step by step."""

    def __init__(self):
        self.spans, self.epochs, self.saves = [], [], []

    def step(self, t, batch=0.010, cpu=9.0, scan=4.0, gather=5.0, major=0,
             epoch=1.0):
        """One optimizer step from ``t``: a loader of ``batch`` seconds of
        which the thread ran ``cpu`` ms, then the epoch."""
        self.spans.append(H.Span(
            H.LOAD, t, t + batch, None,
            {"tokens": 8, "scan_ms": scan, "gather_ms": gather},
            _host(cpu, major=major)))
        t += batch
        self.spans.append(H.Span("penroz/train_epoch", t, t + epoch, None,
                                 {}, _host(1.0)))
        self.epochs.append((t + epoch, 8))
        return t + epoch

    def save(self, t, encode, write, flush, periodic=True):
        """A save from ``t``; ``encode`` / ``write`` / ``flush`` are
        (seconds, cpu ms, sys ms), the flush's seconds ``None`` while it
        runs."""
        t1 = t + encode[0] + write[0]
        i = len(self.spans)
        self.spans.append(H.Span(P.SAVE, t, t1, None,
                                 {"periodic": periodic}, _host(1.0)))
        self.spans.append(H.Span("penroz/ckpt_encode", t, t + encode[0], i,
                                 {}, _host(*encode[1:])))
        self.spans.append(H.Span("penroz/ckpt_write", t + encode[0], t1, i,
                                 {}, _host(*write[1:])))
        self.spans.append(H.Span(
            "penroz/ckpt_flush", t1,
            None if flush[0] is None else t1 + flush[0], i, {},
            _host(*flush[1:])))
        if periodic:
            self.saves.append((t, t1))
        return t1

    def art(self, window, host=True):
        spans = (self.spans if host else
                 [s._replace(host=None, meta={k: v for k, v in s.meta.items()
                                              if not k.endswith("_ms")})
                  for s in self.spans])
        return {"kind": "train", "window": window, "epochs": self.epochs,
                "saves": self.saves, "host_spans": spans,
                "program_spans": [P.Span(*s[:5]) for s in spans]}


def synthetic():
    """Warm-up (a slow loader, a save that ends at the opening); three whole
    cycles, the second with a step whose loader waits 300 ms on 7 major
    faults and the third with an epoch 200 ms long by itself; then a step
    and a save that the window's close cuts."""
    job = Job()
    t = job.step(0.0, batch=2.0, cpu=100.0, major=50)
    opened = t = job.save(t, (1.0, 900.0, 500.0), (1.0, 900.0, 500.0),
                          (2.0, 40.0, 30.0))
    for _ in range(3):                                      # cycle 1: clean
        t = job.step(t)
    t = job.save(t, (2.0, 1990.0, 1500.0), (3.0, 2980.0, 2500.0),
                 (2.0, 50.0, 40.0))
    t = job.step(t)                                         # cycle 2
    t = job.step(t, batch=0.310, cpu=10.0, scan=4.0, gather=305.0, major=7)
    t = job.step(t)
    t = job.save(t, (2.0, 1900.0, 1400.0), (2.0, 1900.0, 1400.0),
                 (3.0, 70.0, 60.0))
    t = job.step(t)                                         # cycle 3
    t = job.step(t, epoch=1.2)
    t = job.step(t)
    job.save(t - 0.5, (0.1, 1.0, 1.0), (0.1, 1.0, 1.0), (0.1, 1.0, 1.0),
             periodic=False)
    closed = t = job.save(t, (4.0, 3000.0, 100.0), (4.0, 3000.0, 100.0),
                          (None, 0.0, 0.0))                 # flush running
    t = job.step(t, batch=5.0, cpu=1.0, major=99)           # after the close
    job.save(t, (9.0, 1.0, 1.0), (9.0, 1.0, 1.0), (9.0, 1.0, 1.0))
    return job, Window(opened, closed, 3, False)


def test_sums_over_a_cycle_and_their_median_over_the_window():
    job, window = synthetic()
    art = job.art(window)
    got = {name: metric_reader(name)(art) for name in READERS}
    # the warm-up's 2 s loader and the 5 s one after the close are out
    assert got["load_batch_ms.max"] == pytest.approx(310.0)
    # a cycle's loaders wait 3 x (10 - 9) ms; the second cycle's 1 + 300 + 1
    assert got["load_batch_ms.wait"] == pytest.approx(3.0)
    assert got["load_batch_ms.scan"] == pytest.approx(12.0)
    assert got["load_batch_ms.gather"] == pytest.approx(15.0)
    assert got["load_batch_major_faults"] == 0
    # epochs of 1 s but one of 1.2: cycles read 0, 0, 200: the median 0
    assert got["save_edge_ms.train_epoch"] == pytest.approx(0.0)
    # periodic saves whole inside: encode + write of 5 s, 4 s and 8 s wall
    # with 4970, 3800 and 6000 ms of CPU, 4000, 2800 and 200 of it sys
    assert got["ckpt_save_ms.wait"] == pytest.approx(200.0)
    assert got["ckpt_save_ms.sys"] == pytest.approx(2800.0)
    # the third save's flush never closed: 50 and 70
    assert got["ckpt_flush_ms.cpu"] == pytest.approx(60.0)


def test_a_thing_that_happens_once_a_cycle_is_not_averaged_away():
    """One cycle to the window (the looped cell's): the step that waits is
    the cycle's sum, not a thirty-third of a median."""
    job, window = synthetic()
    second = Window(job.saves[1][1], job.saves[2][1], 1, False)
    art = job.art(second)
    assert metric_reader("load_batch_ms.wait")(art) == pytest.approx(302.0)
    assert metric_reader("load_batch_ms.gather")(art) == pytest.approx(315.0)
    assert metric_reader("load_batch_major_faults")(art) == 7
    assert metric_reader("load_batch_ms.max")(art) == pytest.approx(310.0)
    assert metric_reader("load_batch_ms")(art) == pytest.approx(10.0)
    third = Window(job.saves[2][1], window.t1, 1, False)
    art = job.art(third)
    # the window's median epoch is 1 s: the cycle's epochs sum to 200 over
    assert metric_reader("save_edge_ms.train_epoch")(art) \
        == pytest.approx(200.0)
    assert metric_reader("ckpt_flush_ms.cpu")(art) is None  # still running
    assert metric_reader("ckpt_save_ms.sys")(art) == pytest.approx(200.0)


def test_a_span_cut_by_a_cycles_edge_is_left_out_whole():
    job, window = synthetic()
    first = Window(window.t0, job.saves[1][1], 1, False)
    # a loader that began before the opening and ended inside
    job.spans.append(H.Span(H.LOAD, first.t0 - 0.5, first.t0 + 0.5, None,
                            {"scan_ms": 500.0, "gather_ms": 500.0},
                            _host(1.0, major=11)))
    art = job.art(first)
    assert metric_reader("load_batch_ms.wait")(art) == pytest.approx(3.0)
    assert metric_reader("load_batch_ms.scan")(art) == pytest.approx(12.0)
    assert metric_reader("load_batch_major_faults")(art) == 0
    assert metric_reader("load_batch_ms.max")(art) == pytest.approx(10.0)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_account_reads_nothing(name):
    """The parent of the PR that added the field: the same spans, no
    ``host``, no ``scan_ms`` / ``gather_ms``.  Nothing is raised and the
    line leaves the metric out, but for the two that read what was there."""
    job, window = synthetic()
    value = metric_reader(name)(job.art(window, host=False))
    assert (value is not None) == (name in OUTSIDE)
    assert metric_reader(name)({"kind": "serve_open"}) is None


@pytest.fixture
def tracing():
    from penroz_tpu.utils import tracing
    tracing.reset()
    yield tracing
    tracing.reset()


def test_walks_the_job_trace_and_keeps_host(tracing):
    """Through the program's own recorder: ``tracing.span`` in a job trace
    of model ``bench`` gives ``host``; a span recorded after the fact
    (``penroz/compile``) and a program without the trace give ``None``."""
    window = Window(0.0, 10.0**9, 1, False)
    empty = {"kind": "train", "window": window}
    assert H.spans(empty) is None
    assert all(metric_reader(n)(dict(empty, epochs=[], saves=[])) is None
               for n in READERS)
    trace = tracing.maybe_trace("j", job=True, route="/train/",
                                model_id=P.MODEL)
    with tracing.use(trace):
        with tracing.span(H.LOAD, tokens=8) as sp:
            sp.set(scan_ms=1.0, gather_ms=2.0)
    late = trace.span("penroz/compile", t0=0.5, seconds=0.25)
    trace.end(late, t1=0.75)
    load, compile_ = H.spans({"kind": "train", "window": window})
    assert load.name == H.LOAD and load.parent is None
    assert load.meta == {"tokens": 8, "scan_ms": 1.0, "gather_ms": 2.0}
    assert set(load.host) == {"cpu_ms", "sys_ms", "major_faults",
                              "minor_faults", "waits", "preempted"}
    assert H.waited_ms(load) >= -0.002      # two roundings
    assert compile_.host is None and compile_.meta == {"seconds": 0.25}
