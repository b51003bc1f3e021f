"""Data and checkpoints: the loader bringing the tokens, a cycle: the sum
over a save cycle's ``penroz/load_batch`` spans of their counter
``gather_ms`` (``Loader.next_batch``: the gather from the mmapped shards,
the copy of the targets, the prefetch of the next pages), the median over
the window's cycles."""

from benchmark.lib import host_account


def read(art):
    return host_account.per_cycle(art, host_account.LOAD,
                                  lambda s: s.meta.get("gather_ms"))
