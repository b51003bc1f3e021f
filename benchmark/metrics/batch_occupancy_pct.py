"""Scheduler: share of the engine's rows that were active, averaged over the
decode steps of the window (``occupancy_avg`` x ``decode_steps``, window
delta)."""


def read(art):
    if art.get("kind") != "serve_open":
        return None
    win = art["window"]
    total = steps = 0.0
    before = {e["replica"]: e for e in win["stats_before"]["engines"]}
    for e in win["stats_after"]["engines"]:
        b = before.get(e["replica"], {"occupancy_avg": 0.0,
                                      "decode_steps": 0})
        total += (e["occupancy_avg"] * e["decode_steps"]
                  - b["occupancy_avg"] * b["decode_steps"])
        steps += e["decode_steps"] - b["decode_steps"]
    return 100.0 * total / steps if steps else None
