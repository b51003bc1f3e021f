"""Traffic of kind ``train_moe_share``: ``kinds/train.py``'s one ``PUT
/train/`` for a sparse-expert model, whole or as one rank's share of an
expert-parallel layer.

Everything is ``train.run``'s; what differs is the count of the work
(``lib/moe_share_costs.py``: layers that differ, and of the routed experts
only the rows the window's epochs really sent to held ones) and what the
expert layers say of themselves: ``penroz/moe_plan`` (the compiled program's
static sizes) and the routing counters of every ``penroz/train_epoch``.  The
artefact keeps ``kind`` ``train``: the accepted readers serve it as they are.
"""

from __future__ import annotations

from benchmark.kinds import train
from benchmark.lib import moe_share_costs, program, program_spans

COUNTERS = ("moe_rows", "moe_rows_padded", "moe_load_max", "moe_dropped")


def moe_plan(art) -> dict | None:
    """The counters of the job's newest ``penroz/moe_plan`` span; ``None``
    from a program that records none."""
    spans = program_spans.spans(art) or []
    found = [s.meta for s in spans if s.name == "penroz/moe_plan"]
    return dict(found[-1]) if found else None


def _summed(took: list) -> dict | None:
    """The counters of the spans ``took`` summed, with the ``epochs`` and
    ``tokens`` they cover; ``None`` for none."""
    if not took:
        return None
    out = {name: sum(s.meta[name] for s in took) for name in COUNTERS}
    return {**out, "epochs": len(took),
            "tokens": sum(s.meta["tokens"] for s in took)}


def _counted_epochs(art) -> list:
    return [s for s in program_spans.spans(art) or []
            if s.name == "penroz/train_epoch" and "moe_rows" in s.meta]


def routing(art) -> dict | None:
    """The routing counters summed over the ``penroz/train_epoch`` spans
    inside the window; ``None`` from a program that counts none."""
    return _summed([s for s in _counted_epochs(art)
                    if program_spans.whole(s, art["window"])])


def traced_routing(art) -> dict | None:
    """The routing counters of the epochs the device trace holds whole, and
    their numbers (``epoch_numbers``).

    The trace has a twin of every ``penroz/train_epoch`` span, opened in
    the same call but on the profiler's clock and without the counters.
    The two clocks differ by a constant, so the traced epochs are the run
    of the job's closed epochs after the window whose starts and durations
    lie as the twins' do (``misfit_ms``: the largest difference left, a
    fraction of a millisecond where the match is right)."""
    trace = art.get("trace")
    if not trace:
        return None
    twins = [(a, b) for name, a, b in trace["planes"]["spans"]
             if name == "penroz/train_epoch"
             and trace["w0"] <= a and b <= trace["w1"]]
    job = [s for s in _counted_epochs(art)
           if s.t1 is not None and s.t0 >= art["window"].t1]
    if not twins or len(job) < len(twins):
        return None

    def misfit(first: int) -> float:
        return max(max(abs((s.t0 - job[first].t0) - (a - twins[0][0])),
                       abs((s.t1 - s.t0) - (b - a)))
                   for s, (a, b) in zip(job[first:], twins))

    first = min(range(len(job) - len(twins) + 1), key=misfit)
    took = job[first:first + len(twins)]
    return {**_summed(took), "misfit_ms": 1000.0 * misfit(first),
            "epoch_numbers": [s.meta.get("epoch") for s in took]}


def run(ctx) -> dict:
    art = train.run(ctx)
    d = program.reference_for(ctx["cfg"]).dims(ctx["cfg"])
    art["moe_plan"] = moe_plan(art)
    art["moe"] = routing(art)
    art["moe_traced"] = traced_routing(art)
    per_token = (art["moe"]["moe_rows"] / art["moe"]["tokens"]
                 if art["moe"] else 0.0)
    art["flops_per_token"] = moe_share_costs.flops_per_token(
        d, art["job"]["block_size"], per_token)
    ctx["say"](phase="moe", plan=art["moe_plan"], window=art["moe"],
               traced=art["moe_traced"],
               routed_rows_per_token=per_token,
               flops_per_token=art["flops_per_token"])
    if art["moe"] and art["moe"]["moe_dropped"]:
        # a dropless layer that lost a pair computed another function
        art["correct"] = False
        art["checks"]["moe_dropped"] = {
            "value": art["moe"]["moe_dropped"], "limit": 0}
    return art
