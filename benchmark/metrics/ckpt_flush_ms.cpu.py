"""Data and checkpoints: what the background copy of a saved checkpoint
costs the host: ``host.cpu_ms`` of ``penroz/ckpt_flush`` (the flush
thread's own account), the median over the window's periodic saves, closed
flushes only.  Beside ``ckpt_flush_ms``: a core burnt, or a thread that
sleeps on the disk."""

from benchmark.lib import host_account


def read(art):
    return host_account.periodic_saves(art, ("penroz/ckpt_flush",),
                                       lambda s: s.host["cpu_ms"])
