"""Per-request lifecycle traces: where did *this* request's time go?

``/serving_stats/`` answers "how is the fleet doing" with aggregates; it
cannot answer "why did request X take 900 ms".  This module gives every
served generation a ``request_id`` (returned in the ``X-Request-Id``
response header and error bodies, bound into log records via a
contextvar) and a **span tree** recording its full lifecycle as the
scheduler drives it:

    request
    ├─ queue            (enqueue → admission)
    ├─ prefill          (admission → first token)
    │  ├─ prefix_match  [event: cached tokens aliased]
    │  ├─ prefill_chunk (one per chunk, size + start position)
    │  └─ ...
    ├─ decode           (first token → retirement)
    │  ├─ decode_step   (per shared tick this row emitted in; capped)
    │  ├─ verify        (spec-decode multi-token step: drafted/accepted)
    │  └─ ...
    ├─ recovery         [events: engine_crash / engine_reset]
    └─ [meta: retire_reason = stop_token | max_new_tokens | timeout |
        cancelled | error | pool_capacity | completed]

Completed traces land in a bounded ring (``PENROZ_TRACE_BUFFER``
entries, default 256) served by ``GET /trace/`` (summaries) and
``GET /trace/{request_id}`` (the span tree; in-flight requests resolve
too).  ``PENROZ_TRACE_SAMPLE`` (0.0–1.0, default 1.0) samples traces at
admission — at 0 the scheduler's per-request overhead is a single
``is None`` check per event site.

Tracing is host-side bookkeeping only: it never touches device buffers,
so greedy outputs are token-identical with tracing on, sampled, or off
(pinned by tests/test_observability.py).

**One span call, two sinks.**  :func:`span` is the one place the program
names a region of its own time.  It always opens a
``jax.profiler.TraceAnnotation`` of exactly that name, so the region has
a twin on the device trace's clock whenever ``/profile/`` (or a benchmark)
captures; and when a trace is *current* in the calling context
(``with tracing.use(trace):``) it records the same interval into that
trace's tree, under the enclosing open span.  A ``PUT /train/`` job is
such a trace (``job=True``): hours long, so it keeps its first top-level
spans (set-up, the compiling first epochs), a ring of the newest, and
per-name totals that never forget:

    request                      [meta: route=/train/, model_id, status]
    ├─ penroz/train_setup        deserialize → placement → loader, programs
    │  └─ penroz/ckpt_save       (status "Training")
    ├─ penroz/load_batch         [tokens, scan_ms, gather_ms]  the loader's
    │                            own account: learning what there is to read
    │                            (glob, stat) / bringing the tokens
    ├─ penroz/train_epoch        [epoch, tokens, sampled, microstepped]
    │  ├─ penroz/train_dispatch  the call of the epoch program
    │  │  └─ penroz/compile      [seconds]  (first epoch only)
    │  └─ penroz/train_wait      float(cost): the device's work
    ├─ penroz/train_stats        [refreshed]
    ├─ penroz/ckpt_save          [tag, periodic, bytes]
    │  ├─ penroz/ckpt_d2h        [bytes, arrays]
    │  ├─ penroz/ckpt_encode     CRC32 pass
    │  ├─ penroz/ckpt_write      [bytes]  header + arrays + rename, to shm
    │  └─ penroz/ckpt_flush      [bytes]  background copy to models/; ends
    │                            after its parent has closed
    └─ ...

**What the thread did inside a span.**  Wall time cannot tell a span that
computed from one that slept on a page read, on a lock, or was not
scheduled.  The OS keeps that account per thread, so every span that
:func:`span` records into a *job* trace carries it as a field of its own,
``host``, beside ``meta`` (which stays what the call site gave):
``getrusage(RUSAGE_THREAD)`` and the thread's CPU clock read at the span's
two ends, the differences as

    cpu_ms         user + system CPU time of the thread: it ran;
                   ``duration_ms - cpu_ms`` is what it waited.  From
                   ``time.thread_time()``, exact to the microsecond:
                   ``ru_utime + ru_stime`` is posted at scheduler ticks and
                   read up to 4 ms more than a span of 2 ms lasted
    sys_ms         of that, in the kernel (page faults, tmpfs, sendfile):
                   ``ru_stime``, the kernel's split by tick sampling, so it
                   means something over tens of ms, not over a short span
    major_faults   pages read from a file system
    minor_faults   fresh or resident pages mapped
    waits          voluntary switches: slept of its own accord (I/O, a
                   lock, the device)
    preempted      involuntary switches: lost the CPU

A span opens and closes on one thread (the flush thread opens and closes
``penroz/ckpt_flush`` itself: its account is the flush's own); the
after-the-fact ``penroz/compile`` has none; a platform without
``RUSAGE_THREAD`` leaves the field out.  The account is as fine as the
host's kernel keeps it: under gVisor (the benchmark's TPU hosts) CPU time
comes in ticks of 10 ms and the four counts read 0, so it resolves a
loader that took 200 ms or a save's passes, not a loader of 4 ms.  With no
job trace current (serving spans, a request's trace, a job sampled out) no
``getrusage`` call is made.
"""

from __future__ import annotations

import collections
import contextvars
import logging
import os
import random
import threading
import time
import uuid

try:
    import resource
except ImportError:     # no POSIX accounting: spans carry no ``host``
    resource = None

import jax

from penroz_tpu.utils import metrics

TRACE_BUFFER_ENV = "PENROZ_TRACE_BUFFER"
TRACE_SAMPLE_ENV = "PENROZ_TRACE_SAMPLE"

# Hard per-trace span cap: a 100k-token generation must not grow an
# unbounded span list — past the cap, spans are counted, not stored.
MAX_SPANS = 1024

# A job trace (``job=True``: one ``PUT /train/``) keeps its first JOB_HEAD
# top-level spans for good (set-up and the first epochs, where programs
# compile) and a ring of the JOB_RING newest; whole subtrees leave from
# the ring's old end.  Two top-level spans an epoch: the ring is the last
# ~2000 epochs, a few MB.
JOB_HEAD = 8
JOB_RING = 4096

# Every job span's duration by name, over the life of the process:
# ``penroz_train_span_ms{span=...}`` on GET /metrics (registered by
# serve/metrics.py).  The ring forgets; this does not.
TRAIN_SPAN_MS = metrics.Histogram(
    "penroz_train_span_ms",
    "Duration of each span of /train/ jobs by span name "
    "(utils/tracing.py: train_epoch, ckpt_save and its children, ...), ms",
    labelnames=("span",))

# What the modules of a model report of a training epoch, by the ``family``
# each statistic's declaration names: ``penroz_train_<family>{<label>}`` on
# GET /metrics (registered by serve/metrics.py), the newest epoch's reading.
# A statistic with one value a pass has a family of its own, labelled by the
# pass (from 1); scalars share a family, labelled by their names.
TRAIN_FAMILIES = {
    "pass_loss": ("pass", "Mean cross-entropy of each exit of a looped "
                  "model, newest /train/ epoch"),
    "exit_mass": ("pass", "Mean exit distribution of a looped model over "
                  "its passes, newest /train/ epoch"),
    "moe": ("counter", "Counts of the newest /train/ epoch, summed over "
            "layers and micro-steps (the dropless expert layers' routing)"),
    "hc": ("counter", "Largest values of the newest /train/ epoch over "
           "layers and micro-steps (a multi-stream residual's Sinkhorn "
           "error, a router's selection bias)"),
    "ssd": ("counter", "Largest values of the newest /train/ epoch over "
            "layers and micro-steps (a Mamba-2 mixer's step size and the "
            "log-decay summed over a chunk)"),
}
_TRAIN_STATS: dict = {}     # family -> {label value: newest reading}
TRAIN_STAT_GAUGES = [
    metrics.Gauge(f"penroz_train_{family}", text,
                  fn=lambda family=family: _TRAIN_STATS.get(family, {}),
                  labelnames=(label,))
    for family, (label, text) in TRAIN_FAMILIES.items()]


def train_stat_counters(stats: dict, families: dict) -> dict:
    """One epoch's statistics ``{name: value}`` as counters of its
    ``penroz/train_epoch`` span: a list, one value a pass, as
    ``<name>_<t>`` from 1, a scalar under its own name.  The same values
    become the newest reading of ``families[name]``; a family this epoch
    brings nothing for keeps its last."""
    counters, fresh = {}, {}
    for name, value in stats.items():
        rows = ([(str(t), f"{name}_{t}", v) for t, v in enumerate(value, 1)]
                if isinstance(value, list) else [(name, name, value)])
        for label, counter, v in rows:
            fresh.setdefault(families[name], {})[label] = v
            counters[counter] = v
    _TRAIN_STATS.update(fresh)
    return counters


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_request_id_var: contextvars.ContextVar = contextvars.ContextVar(
    "penroz_request_id", default=None)

_lock = threading.Lock()
_completed: collections.deque = collections.deque(maxlen=256)
_completed_maxlen = 256
_live: dict = {}


def _buffer_size() -> int:
    try:
        return max(1, int(os.environ.get(TRACE_BUFFER_ENV, "256")))
    except ValueError:
        return 256


def _sample_rate() -> float:
    try:
        return min(1.0, max(0.0, float(
            os.environ.get(TRACE_SAMPLE_ENV, "1.0"))))
    except ValueError:
        return 1.0


# -- request-id plumbing ----------------------------------------------------

def new_request_id(supplied: str | None = None) -> str:
    """A fresh request id — or the client's own ``X-Request-Id`` when it
    sent a sane one (correlating proxy/server logs beats uniqueness)."""
    if supplied:
        supplied = supplied.strip()
        if 0 < len(supplied) <= 64 and all(
                c.isalnum() or c in "-_." for c in supplied):
            return supplied
    return uuid.uuid4().hex


def bind(request_id: str | None):
    """Bind ``request_id`` into the logging contextvar; returns the token
    for :func:`unbind`."""
    return _request_id_var.set(request_id)


def unbind(token) -> None:
    _request_id_var.reset(token)


def current_request_id() -> str | None:
    return _request_id_var.get()


class RequestIdFilter(logging.Filter):
    """Stamps ``record.request_id`` from the contextvar (``-`` outside any
    request) so formats can carry ``%(request_id)s`` — referenced by
    log_config.json."""

    def filter(self, record: logging.LogRecord) -> bool:
        record.request_id = _request_id_var.get() or "-"
        return True


# -- spans ------------------------------------------------------------------

def _thread_usage():
    """The calling thread's account so far: its CPU clock and
    ``getrusage(RUSAGE_THREAD)`` (under a microsecond for the two);
    ``None`` where the platform keeps no per-thread account."""
    who = getattr(resource, "RUSAGE_THREAD", None)
    if who is None:
        return None
    return time.thread_time(), resource.getrusage(who)


def _host_account(begin, end) -> dict:
    """What the thread did between two :func:`_thread_usage` readings
    (module docstring)."""
    (cpu0, ru0), (cpu1, ru1) = begin, end
    return {
        "cpu_ms": round(1000.0 * (cpu1 - cpu0), 3),
        "sys_ms": round(1000.0 * (ru1.ru_stime - ru0.ru_stime), 3),
        "major_faults": ru1.ru_majflt - ru0.ru_majflt,
        "minor_faults": ru1.ru_minflt - ru0.ru_minflt,
        "waits": ru1.ru_nvcsw - ru0.ru_nvcsw,
        "preempted": ru1.ru_nivcsw - ru0.ru_nivcsw,
    }


class Span:
    __slots__ = ("name", "t0", "t1", "meta", "host", "children", "detached")

    def __init__(self, name: str, t0: float, meta: dict | None = None):
        self.name = name
        self.t0 = t0
        self.t1: float | None = None
        self.meta = meta or {}
        self.host: dict | None = None   # the thread's account (job spans)
        self.children: list[Span] = []
        self.detached = False   # its subtree has left a job trace's ring

    def to_dict(self, base: float) -> dict:
        out = {
            "name": self.name,
            "t0_ms": round((self.t0 - base) * 1000.0, 3),
            "t1_ms": (round((self.t1 - base) * 1000.0, 3)
                      if self.t1 is not None else None),
            "duration_ms": (round((self.t1 - self.t0) * 1000.0, 3)
                            if self.t1 is not None else None),
        }
        if self.meta:
            out["meta"] = dict(self.meta)
        if self.host is not None:
            out["host"] = dict(self.host)
        if self.children:
            out["children"] = [c.to_dict(base) for c in self.children]
        return out


def _detach(sp: Span) -> int:
    """Mark a subtree as gone from its trace; returns its size."""
    sp.detached = True
    return 1 + sum(_detach(c) for c in sp.children)


class Trace:
    """One request's span tree.  All mutation goes through methods that
    take the trace lock — spans arrive from the scheduler worker thread
    while the HTTP layer may be serializing the in-flight tree.

    ``job=True`` is a long-running job's trace (a ``PUT /train/``): the
    root keeps its first ``JOB_HEAD`` children and the ``JOB_RING`` newest
    (a request keeps the *oldest* ``MAX_SPANS``, which for a job of
    600 000 epochs would be its first minutes), counts what left in
    ``dropped_spans``, and folds every closed span into ``totals``."""

    def __init__(self, request_id: str, job: bool = False, **meta):
        self.request_id = request_id
        self.started_unix = time.time()
        self.t0 = time.monotonic()
        self.meta = dict(meta)
        self.root = Span("request", self.t0)
        self._lock = threading.Lock()
        self._finished = False
        self._span_count = 1
        self.dropped_spans = 0
        self.job = job
        # span name -> metrics.Hist of durations in ms (job traces only)
        self.totals: dict = {}
        # Set by the scheduler once the request is accepted into its
        # queue: from then on the ENGINE guarantees the finish (retire /
        # shed / crash recovery), and the HTTP layer must not finish the
        # trace early — a crash's recovery span is recorded after the
        # error event has already been delivered to the client.
        self.owned = False

    # -- recording (scheduler-side) ----------------------------------------

    def span(self, name: str, t0: float | None = None,
             parent: Span | None = None, **meta) -> Span | None:
        """Open a child span under ``parent`` (the root by default).
        Returns None past the per-trace cap (counted in dropped_spans)."""
        with self._lock:
            if self._finished:
                return None
            if not self.job and self._span_count >= MAX_SPANS:
                self.dropped_spans += 1
                return None
            sp = Span(name, t0 if t0 is not None else time.monotonic(), meta)
            if parent is not None and parent.detached:
                # a late child (a flush) of a subtree the ring has let go:
                # timed for the totals, held nowhere
                sp.detached = True
                self.dropped_spans += 1
                return sp
            siblings = (parent or self.root).children
            siblings.append(sp)
            self._span_count += 1
            if (self.job and siblings is self.root.children
                    and len(siblings) > JOB_HEAD + JOB_RING):
                gone = _detach(siblings.pop(JOB_HEAD))
                self._span_count -= gone
                self.dropped_spans += gone
            return sp

    def end(self, sp: Span | None, t1: float | None = None,
            host: dict | None = None, **meta) -> None:
        if sp is None:
            return
        with self._lock:
            sp.t1 = t1 if t1 is not None else time.monotonic()
            if meta:
                sp.meta.update(meta)
            if host is not None:
                sp.host = host
            if not self.job:
                return
            hist = self.totals.get(sp.name)
            if hist is None:
                hist = self.totals[sp.name] = metrics.Hist()
            ms = (sp.t1 - sp.t0) * 1000.0
            hist.observe(ms)
        TRAIN_SPAN_MS.observe(ms, span=sp.name)

    def event(self, name: str, parent: Span | None = None, **meta) -> None:
        """Point-in-time marker: a zero-length span."""
        now = time.monotonic()
        sp = self.span(name, t0=now, parent=parent, **meta)
        self.end(sp, t1=now)

    def annotate(self, **meta) -> None:
        with self._lock:
            self.meta.update(meta)

    def finish(self, reason: str | None = None) -> None:
        """Close the root span and move the trace to the completed ring.
        Idempotent — the first finish wins (the scheduler retires the
        request; a belt-and-braces handler finish is then a no-op)."""
        with self._lock:
            if self._finished:
                return
            self._finished = True
            self.root.t1 = time.monotonic()
            if reason is not None:
                self.meta.setdefault("retire_reason", reason)
        _complete(self)

    @property
    def finished(self) -> bool:
        return self._finished

    # -- serialization (HTTP-side) -----------------------------------------

    def summary(self) -> dict:
        with self._lock:
            dur = (self.root.t1 if self.root.t1 is not None
                   else time.monotonic()) - self.t0
            return {
                "request_id": self.request_id,
                "started_unix": round(self.started_unix, 3),
                "duration_ms": round(dur * 1000.0, 3),
                "finished": self._finished,
                "spans": self._span_count,
                **{k: v for k, v in self.meta.items()},
            }

    def to_dict(self) -> dict:
        with self._lock:
            return {
                "request_id": self.request_id,
                "started_unix": round(self.started_unix, 3),
                "finished": self._finished,
                "meta": dict(self.meta),
                "dropped_spans": self.dropped_spans,
                "root": self.root.to_dict(self.t0),
                # quantiles of a bucket histogram: the bucket's upper edge
                **({"totals": {
                    name: {"count": h.count, "sum_ms": round(h.sum, 3),
                           "mean_ms": round(h.sum / h.count, 3),
                           "p50_le_ms": round(h.quantile(0.5), 3),
                           "p99_le_ms": round(h.quantile(0.99), 3),
                           "max_ms": round(h.max, 3)}
                    for name, h in sorted(self.totals.items())}}
                   if self.job else {}),
            }

    def to_chrome(self) -> dict:
        """The span tree as Chrome trace-event JSON (the ``traceEvents``
        array format) — ``GET /trace/{id}?format=chrome`` loads directly
        into Perfetto / chrome://tracing.  Complete events (``ph: "X"``)
        with microsecond ``ts`` relative to the trace start (monotonic,
        so events never go backwards); ``pid`` is the request id and
        ``tid`` the span depth, which renders the tree as nested tracks.
        In-flight spans clamp to "now" — a live snapshot is still a
        valid, loadable file."""
        with self._lock:
            now = time.monotonic()
            events = []
            stack = [(self.root, 0)]
            while stack:
                sp, depth = stack.pop()
                t1 = sp.t1 if sp.t1 is not None else now
                ev = {
                    "name": sp.name,
                    "ph": "X",
                    "ts": round((sp.t0 - self.t0) * 1e6, 1),
                    "dur": round(max(0.0, t1 - sp.t0) * 1e6, 1),
                    "pid": self.request_id,
                    "tid": depth,
                }
                args = dict(sp.meta)
                if sp.host is not None:
                    args["host"] = dict(sp.host)
                if sp is self.root:
                    args.update(self.meta)
                    args["started_unix"] = round(self.started_unix, 3)
                if args:
                    ev["args"] = args
                events.append(ev)
                stack.extend((c, depth + 1) for c in sp.children)
            events.sort(key=lambda e: e["ts"])
            return {"traceEvents": events, "displayTimeUnit": "ms"}


# -- one span call, two sinks -----------------------------------------------

# (trace, innermost open span or None) of the calling context.  A thread
# started on the job's behalf does not inherit it: take it with
# :func:`capture` at spawn and bind it inside with :func:`use`.
_binding_var: contextvars.ContextVar = contextvars.ContextVar(
    "penroz_trace_binding", default=None)

_listener_lock = threading.Lock()
_listener_registered = False


class span:
    """``with tracing.span("penroz/ckpt_write", bytes=n) as sp:`` — a named
    region of the program's own time, to both sinks (module docstring).

    The ``TraceAnnotation`` carries exactly ``name`` and no metadata (the
    benchmark's trace reduction matches ``penroz/*`` names by equality);
    ``counters`` go to the current trace's span ``meta`` only, as do those
    given later through :meth:`set`; in a job trace the span also gets the
    calling thread's own account as ``host`` (module docstring).  With no
    current trace this is the annotation and nothing more.  :meth:`close`
    ends the span before its ``with`` block does (set-up that hands over to
    a loop) and is idempotent.  Failures of the profiler never reach the
    caller."""

    __slots__ = ("name", "_counters", "_ann", "_outer", "_span", "_usage")

    def __init__(self, name: str, **counters):
        self.name = name
        self._counters = counters
        self._ann = None
        self._outer = None      # the binding to restore at close
        self._span = None
        self._usage = None      # the thread's account at the span's opening

    def __enter__(self):
        try:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        except Exception:  # noqa: BLE001 — profiling must never break the path
            self._ann = None
        outer = _binding_var.get()
        if outer is not None:
            trace, parent = outer
            self._outer = outer
            self._span = trace.span(self.name, parent=parent,
                                    **self._counters)
            self._counters = {}     # from here on: what set() brings
            if self._span is not None:
                _binding_var.set((trace, self._span))
                if trace.job:
                    self._usage = _thread_usage()
        return self

    def set(self, **counters) -> None:
        """Counters known only once the work is done (bytes written)."""
        if self._outer is not None:
            self._counters.update(counters)

    def close(self) -> None:
        outer, self._outer = self._outer, None
        if outer is not None:
            begin, self._usage = self._usage, None
            now = _thread_usage() if begin is not None else None
            host = _host_account(begin, now) if now is not None else None
            outer[0].end(self._span, host=host, **self._counters)
            _binding_var.set(outer)
        ann, self._ann = self._ann, None
        if ann is not None:
            try:
                ann.__exit__(None, None, None)
            except Exception:  # noqa: BLE001
                pass

    def __exit__(self, *exc):
        self.close()


def capture():
    """The calling context's binding, to hand to a thread (``None`` when no
    trace is current): spans the thread opens under ``use(binding)`` become
    children of the span that was open *here*, though it may have closed by
    the time they end (``penroz/ckpt_flush`` under its ``ckpt_save``)."""
    return _binding_var.get()


class use:
    """``with tracing.use(trace):`` makes ``trace`` (a :class:`Trace`, a
    :func:`capture` result, or ``None``: no-op) current for the block, so
    that :func:`span` and the compile listener record into it."""

    __slots__ = ("_binding", "_token")

    def __init__(self, trace):
        self._binding = ((trace, None) if isinstance(trace, Trace)
                         else trace)
        self._token = None

    def __enter__(self):
        if self._binding is not None:
            _register_compile_listener()
            self._token = _binding_var.set(self._binding)
        return self

    def __exit__(self, *exc):
        if self._token is not None:
            _binding_var.reset(self._token)


def _on_event_duration(event: str, duration: float, **_kwargs) -> None:
    """``jax.monitoring`` listener: a backend compile (jax 0.9.0 reports it
    from the compiling thread, so the binding is there) becomes a closed
    ``penroz/compile`` span under the current span.  A persistent-cache hit
    is reported too, with the time the retrieval took."""
    if event != COMPILE_EVENT:
        return
    binding = _binding_var.get()
    if binding is None:
        return
    trace, parent = binding
    now = time.monotonic()
    sp = trace.span("penroz/compile", t0=now - duration, parent=parent,
                    seconds=round(duration, 6))
    trace.end(sp, t1=now)


def _register_compile_listener() -> None:
    global _listener_registered
    if _listener_registered:
        return
    with _listener_lock:
        if not _listener_registered:
            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            _listener_registered = True


# -- registry ---------------------------------------------------------------

def maybe_trace(request_id: str, job: bool = False,
                **meta) -> Trace | None:
    """Start a trace for ``request_id`` under the sampling rate (None when
    sampled out — every recording site is None-guarded, so the disabled
    path costs one comparison).  ``job=True``: see :class:`Trace`."""
    rate = _sample_rate()
    if rate <= 0.0 or (rate < 1.0 and random.random() >= rate):
        return None
    trace = Trace(request_id, job=job, **meta)
    with _lock:
        _live[request_id] = trace
    return trace


def _complete(trace: Trace) -> None:
    global _completed, _completed_maxlen
    with _lock:
        _live.pop(trace.request_id, None)
        size = _buffer_size()
        if size != _completed_maxlen:
            _completed = collections.deque(_completed, maxlen=size)
            _completed_maxlen = size
        _completed.append(trace)
    try:  # scrape counter; utils must not hard-require the serve layer
        from penroz_tpu.serve import metrics as serve_metrics
        serve_metrics.TRACES_COMPLETED.inc()
    except Exception:  # noqa: BLE001 — pragma: no cover
        pass


def get(request_id: str) -> Trace | None:
    """Look up a trace by id — in-flight first, then the completed ring."""
    with _lock:
        trace = _live.get(request_id)
        if trace is not None:
            return trace
        for t in reversed(_completed):
            if t.request_id == request_id:
                return t
    return None


def completed(limit: int = 100) -> list[Trace]:
    """Most-recent-first completed traces (ring order)."""
    with _lock:
        out = list(_completed)
    out.reverse()
    return out[:max(0, limit)]


def live() -> list[Trace]:
    with _lock:
        return list(_live.values())


def reset() -> None:
    """Drop all trace state (tests)."""
    global _completed, _completed_maxlen
    with _lock:
        _completed = collections.deque(maxlen=_buffer_size())
        _completed_maxlen = _completed.maxlen
        _live.clear()
