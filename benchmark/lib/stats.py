"""Quantiles of samples, and of the window delta of a bucket histogram."""

from __future__ import annotations

import statistics


def quantile(values: list, q: float) -> float | None:
    """``q`` in (0, 1) by ``statistics.quantiles`` (exclusive method) on a
    grid of 100; the single value for one sample; ``None`` for none."""
    if not values:
        return None
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=100)[round(q * 100) - 1])


def hist_delta(after: dict, before: dict | None) -> dict:
    """What a ``Hist.snapshot()`` gained between two readings."""
    if before is None:
        return dict(after)
    return {"buckets": list(after["buckets"]),
            "counts": [a - b for a, b in zip(after["counts"],
                                             before["counts"])],
            "sum": after["sum"] - before["sum"],
            "count": after["count"] - before["count"],
            "max": after["max"]}


def hist_quantile(snapshot: dict, q: float) -> float | None:
    """``q``-quantile of a bucket histogram (``buckets`` are upper edges),
    interpolated linearly inside the bucket it falls in, and never above the
    largest value seen."""
    count = snapshot["count"]
    if not count:
        return None
    target, cum, lower = q * count, 0, 0.0
    for edge, c in zip(snapshot["buckets"], snapshot["counts"]):
        if c and cum + c >= target:
            value = lower + (edge - lower) * (target - cum) / c
            cap = snapshot.get("max")
            return min(value, cap) if cap is not None else value
        cum += c
        lower = edge
    return snapshot.get("max")


def engines_hist_delta(stats_after: dict, stats_before: dict,
                       name: str) -> dict | None:
    """The gain of histogram ``name`` between two in-process
    ``decode_scheduler.serving_stats()`` readings, summed over engines."""
    before = {e["replica"]: e for e in stats_before["engines"]}
    merged = None
    for e in stats_after["engines"]:
        prior = before.get(e["replica"])
        delta = hist_delta(e["histograms"][name],
                           prior["histograms"][name] if prior else None)
        if merged is None:
            merged = delta
            continue
        merged = {"buckets": merged["buckets"],
                  "counts": [a + b for a, b in zip(merged["counts"],
                                                   delta["counts"])],
                  "sum": merged["sum"] + delta["sum"],
                  "count": merged["count"] + delta["count"],
                  "max": max((x for x in (merged["max"], delta["max"])
                              if x is not None), default=None)}
    return merged
