"""Where JAX's persistent compilation cache lives — the one rule.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing is set
in code, so whoever launches the process places the cache (a machine that
is thrown away after each run keeps its compiled programs only at a path
its caller controls).  Unset: a fixed directory inside the checkout.  The
path is part of the cache key, so it is never built from a temp name, a
pid, a clock or a host fingerprint.

Imports no jax at module level: parents that must stay off the accelerator
(``__graft_entry__.dryrun_multichip``) call :func:`cache_dir` to hand the
same directory to their children.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The directory in effect: the environment's, else the in-checkout
    default."""
    return os.environ.get(ENV) or DEFAULT_DIR


def configure() -> str:
    """Turn the persistent cache on for this process; returns its
    directory.  A failure to create or set it raises — a server that
    silently recompiles every program on every start is a fault, not a
    mode."""
    path = cache_dir()
    if not os.environ.get(ENV):
        import jax
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
