"""Data and checkpoints: what the training thread *waited* inside the
loader, a cycle: the sum over a save cycle's ``penroz/load_batch`` spans of
duration - ``host.cpu_ms`` (the thread's own CPU time, read at the span's
two ends), the median over the window's cycles.  A page read, a lock, a
thread not scheduled; not the glob or the copy, which run."""

from benchmark.lib import host_account


def read(art):
    return host_account.per_cycle(
        art, host_account.LOAD,
        lambda s: host_account.waited_ms(s) if s.host is not None else None)
