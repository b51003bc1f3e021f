"""Profiling hooks over ``jax.profiler``.

The reference has no tracer/profiler integration — its closest facility is
per-epoch wall-time + tokens/sec logging (neural_net_model.py:683-703),
which this framework also keeps (progress records).  SURVEY.md §5 calls for
a real profile hook on top: these helpers expose

- ``start(log_dir)`` / ``stop()`` — capture an XLA/TPU trace viewable in
  TensorBoard or Perfetto (device kernels, HBM transfers, host callbacks);
- ``maybe_start_server()`` — a live-profiling gRPC endpoint
  (``PENROZ_PROFILER_PORT``) for `tensorboard --logdir` capture on a
  running service.

The named regions a capture shows are ``utils/tracing.py::span``'s.

All helpers are no-op-safe: profiling failures must never take down
training or serving.
"""

from __future__ import annotations

import logging
import os
import threading

import jax

log = logging.getLogger(__name__)

PROFILER_PORT_ENV = "PENROZ_PROFILER_PORT"

_lock = threading.Lock()
_active_dir: str | None = None
_server_started = False


def is_active() -> bool:
    return _active_dir is not None


def start(log_dir: str) -> bool:
    """Begin a trace capture into ``log_dir``; False when a capture is
    already running — ours, or one owned by another controller (e.g. a
    TensorBoard client on the ``maybe_start_server`` endpoint)."""
    global _active_dir
    with _lock:
        if _active_dir is not None:
            return False
        try:
            jax.profiler.start_trace(log_dir)
        except RuntimeError as e:
            # JAX-level "profiler already active" from an external session.
            log.warning("start_trace refused: %s", e)
            return False
        _active_dir = log_dir
        log.info("Profiler trace started → %s", log_dir)
        return True


def stop() -> str | None:
    """End the running capture; returns its log dir (None if idle).

    State clears only on success: if trace serialization fails (disk full),
    ``_active_dir`` is kept so a retried stop can still reach the wedged
    session instead of reporting "nothing running"."""
    global _active_dir
    with _lock:
        if _active_dir is None:
            return None
        log_dir = _active_dir
        jax.profiler.stop_trace()
        _active_dir = None
        log.info("Profiler trace stopped → %s", log_dir)
        return log_dir


def maybe_start_server() -> bool:
    """Start the live-capture gRPC server when PENROZ_PROFILER_PORT is set."""
    global _server_started
    port = os.environ.get(PROFILER_PORT_ENV)
    if not port or _server_started:
        return False
    try:
        jax.profiler.start_server(int(port))
        _server_started = True
        log.info("jax.profiler server listening on :%s", port)
        return True
    except Exception as e:  # noqa: BLE001
        log.warning("Could not start profiler server on %s: %s", port, e)
        return False
