"""Taking a profiler trace of a short window in this process."""

from __future__ import annotations

import glob
import os
import time


class Trace:
    """``with Trace(dir) as t: ...`` then ``t.path`` is the ``.xplane.pb``
    and ``t.t0``/``t.t1`` the host's monotonic clock at start and stop."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.path = None
        self.t0 = self.t1 = 0.0

    def start(self):
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans only: small traces
        options.host_tracer_level = 2
        os.makedirs(self.log_dir, exist_ok=True)
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.t0 = time.monotonic()
        return self

    def stop(self):
        import jax
        self.t1 = time.monotonic()
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                               f"{self.log_dir}")
        self.path = found[-1]
        return self

    __enter__ = start

    def __exit__(self, *exc):
        self.stop()
