"""Traffic of kind ``train_mla_share``: ``kinds/train_moe_share.py``'s run of
``kinds/train.py``'s one ``PUT /train/`` for a sparse-expert model with
latent attention and a multi-stream residual, whole or as one rank's share.

Everything measured is ``train.run``'s, and what the expert layers say of
themselves is read by ``train_moe_share``'s functions (``penroz/moe_plan``,
the routing counters, ``moe_dropped`` must be 0, the traced epochs'
counters); what differs is the count of the work (``lib/mla_share_costs.py``:
latent attention's five matrices, unlike score and value widths, the stream
mixing's projection) and two more things the program says of itself: the
``penroz/latent_plan`` and ``penroz/hc_plan`` spans and the
``hc_sinkhorn_err`` / ``moe_bias_absmax`` counters of every
``penroz/train_epoch`` (``None`` from a program that records none).  The
artefact keeps ``kind`` ``train``: the accepted readers serve it as they are.
"""

from __future__ import annotations

from benchmark.kinds import train
from benchmark.kinds.train_moe_share import routing, traced_routing
from benchmark.lib import mla_share_costs, program, program_spans

PEAKS = ("hc_sinkhorn_err", "moe_bias_absmax")


def plan_of(art, name: str) -> dict | None:
    """The counters of the job's newest span ``name``; ``None`` from a
    program that records none."""
    found = [s.meta for s in program_spans.spans(art) or []
             if s.name == name]
    return dict(found[-1]) if found else None


def peaks(art) -> dict | None:
    """The largest of each of :data:`PEAKS` over the ``penroz/train_epoch``
    spans inside the window, and how many carried it; ``None`` from a
    program that counts none."""
    took = [s for s in program_spans.spans(art) or []
            if s.name == "penroz/train_epoch"
            and any(name in s.meta for name in PEAKS)
            and program_spans.whole(s, art["window"])]
    if not took:
        return None
    out = {name: max(s.meta[name] for s in took if name in s.meta)
           for name in PEAKS if any(name in s.meta for s in took)}
    return {**out, "epochs": len(took)}


def run(ctx) -> dict:
    art = train.run(ctx)
    d = program.reference_for(ctx["cfg"]).dims(ctx["cfg"])
    art["moe_plan"] = plan_of(art, "penroz/moe_plan")
    art["moe"] = routing(art)
    art["moe_traced"] = traced_routing(art)
    art["latent_plan"] = plan_of(art, "penroz/latent_plan")
    art["hc_plan"] = plan_of(art, "penroz/hc_plan")
    art["peaks_counted"] = peaks(art)
    per_token = (art["moe"]["moe_rows"] / art["moe"]["tokens"]
                 if art["moe"] else 0.0)
    art["flops_per_token"] = mla_share_costs.flops_per_token(
        d, art["job"]["block_size"], per_token)
    ctx["say"](phase="mla", moe_plan=art["moe_plan"],
               latent_plan=art["latent_plan"], hc_plan=art["hc_plan"],
               window=art["moe"], traced=art["moe_traced"],
               peaks=art["peaks_counted"], routed_rows_per_token=per_token,
               flops_per_token=art["flops_per_token"],
               forward_flops_per_token=mla_share_costs
               .forward_flops_per_token(d, art["job"]["block_size"],
                                        per_token))
    if art["moe"] and art["moe"]["moe_dropped"]:
        # a dropless layer that lost a pair computed another function
        art["correct"] = False
        art["checks"]["moe_dropped"] = {
            "value": art["moe"]["moe_dropped"], "limit": 0}
    return art
