"""Scheduler: time a request waited in the admission queue, median of the
engine's ``queue_wait_ms`` histogram over the window (window delta of the bucket
counts, interpolated inside the bucket: ticks that compiled in warm-up are
out)."""

from benchmark.lib import stats


def read(art):
    if art.get("kind") != "serve_open":
        return None
    win = art["window"]
    h = stats.engines_hist_delta(win["stats_after"], win["stats_before"],
                                 "queue_wait_ms")
    return stats.hist_quantile(h, 0.5) if h else None
