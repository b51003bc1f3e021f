"""Data and checkpoints: pages the loader had to read from a file system, a
cycle: the sum over a save cycle's ``penroz/load_batch`` spans of
``host.major_faults`` (the training thread's own), the median over the
window's cycles.  0 where the prefetch had brought them."""

from benchmark.lib import host_account


def read(art):
    return host_account.per_cycle(
        art, host_account.LOAD,
        lambda s: s.host["major_faults"] if s.host is not None else None)
