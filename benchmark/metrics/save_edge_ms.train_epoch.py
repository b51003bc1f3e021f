"""Model runtime: the part of a save cycle's edge that is *not* the loader:
the sum over a cycle's ``penroz/train_epoch`` spans of duration - the
window's median duration, the median over the window's cycles.  A step that
stalls by itself shows here; one whose loader waits shows in
``load_batch_ms.wait``; with the bookkeeping before the save
(``penroz/train_stats``) the two are the inside of ``save_edge_ms``."""

from benchmark.lib import host_account, program_spans

EPOCH = "penroz/train_epoch"


def read(art):
    steady = program_spans.span_ms(art, EPOCH)
    if steady is None:
        return None
    return host_account.per_cycle(
        art, EPOCH, lambda s: 1000.0 * (s.t1 - s.t0) - steady)
