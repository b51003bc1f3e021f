"""Data and checkpoints: the loader learning what there is to read, a
cycle: the sum over a save cycle's ``penroz/load_batch`` spans of their
counter ``scan_ms`` (``Loader.next_batch``: the glob of ``data/`` and the
``stat`` of every shard, on every micro-batch), the median over the
window's cycles."""

from benchmark.lib import host_account


def read(art):
    return host_account.per_cycle(art, host_account.LOAD,
                                  lambda s: s.meta.get("scan_ms"))
