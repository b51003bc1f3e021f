"""Traffic of kind ``train_looped``: ``kinds/train.py``'s one ``PUT /train/``
for a model whose layers run several times a token (a looped stack).

Everything is ``train.run``'s; what differs is the count of the work
(``lib/looped_costs.py``: every layer and the head once per pass) and what
the loop's own span says of the compiled program (``penroz/loop_plan``).
The artefact keeps ``kind`` ``train``: the accepted readers serve it as they
are.
"""

from __future__ import annotations

from benchmark.kinds import train
from benchmark.lib import looped_costs, program, program_spans


def loop_plan(art) -> dict | None:
    """The counters of the job's newest ``penroz/loop_plan`` span (one a
    compile, under the compiling epoch); ``None`` from a program that
    records none."""
    spans = program_spans.spans(art) or []
    found = [s.meta for s in spans if s.name == "penroz/loop_plan"]
    return dict(found[-1]) if found else None


def run(ctx) -> dict:
    art = train.run(ctx)
    d = program.reference_for(ctx["cfg"]).dims(ctx["cfg"])
    art["flops_per_token"] = looped_costs.flops_per_token(
        d["d"], d["heads"], d["head_dim"], d["intermediate"], d["depth"],
        d["steps"], d["vocab"], art["job"]["block_size"])
    art["loop_plan"] = loop_plan(art)
    ctx["say"](phase="loop_plan", plan=art["loop_plan"],
               flops_per_token=art["flops_per_token"])
    return art
