"""Traffic of kind ``train_ssm_share``: ``kinds/train_moe_share.py``'s run of
``kinds/train.py``'s one ``PUT /train/`` for a hybrid model whose layers are
each one mixer (a Mamba-2 state-space mixer, a LatentMoE, attention), whole
or as one rank's share.

Everything measured is ``train.run``'s, and what the expert layers say of
themselves is read by ``train_moe_share``'s functions (``penroz/moe_plan``,
the routing counters, ``moe_dropped`` must be 0, the traced epochs'
counters); what differs is the count of the work (``lib/ssm_share_costs.py``:
the mixer's two matrices and its scan, experts of two matrices in a latent)
and what the mixers say of themselves: the ``penroz/ssd_plan`` span and the
``ssd_log_decay_absmax`` / ``ssd_dt_max`` counters of every
``penroz/train_epoch`` (``None`` from a program that records none).  The
artefact keeps ``kind`` ``train``: the accepted readers serve it as they are.
"""

from __future__ import annotations

from benchmark.kinds import train
from benchmark.kinds.train_mla_share import plan_of
from benchmark.kinds.train_moe_share import routing, traced_routing
from benchmark.lib import program, program_spans, ssm_share_costs

PEAKS = ("ssd_log_decay_absmax", "ssd_dt_max", "moe_bias_absmax")


def _counted_epochs(art) -> list:
    """The job's ``penroz/train_epoch`` spans that carry one of
    :data:`PEAKS`, in order."""
    return [s for s in program_spans.spans(art) or []
            if s.name == "penroz/train_epoch"
            and any(name in s.meta for name in PEAKS)]


def peaks(art) -> dict | None:
    """The largest of each of :data:`PEAKS` over the ``penroz/train_epoch``
    spans inside the window, and how many carried one; ``None`` from a
    program that counts none."""
    took = [s for s in _counted_epochs(art)
            if program_spans.whole(s, art["window"])]
    if not took:
        return None
    out = {name: max(s.meta[name] for s in took if name in s.meta)
           for name in PEAKS if any(name in s.meta for s in took)}
    return {**out, "epochs": len(took)}


def first_epoch(art) -> dict | None:
    """:data:`PEAKS` as the job's first ``penroz/train_epoch`` span that
    carries one reads them: the seeded weights' (the window's are the
    weights' the job has reached); ``None`` from a program that counts
    none."""
    for s in _counted_epochs(art)[:1]:
        return {"epoch": s.meta.get("epoch"),
                **{n: s.meta[n] for n in PEAKS if n in s.meta}}
    return None


def run(ctx) -> dict:
    art = train.run(ctx)
    d = program.reference_for(ctx["cfg"]).dims(ctx["cfg"])
    art["moe_plan"] = plan_of(art, "penroz/moe_plan")
    art["ssd_plan"] = plan_of(art, "penroz/ssd_plan")
    art["moe"] = routing(art)
    art["moe_traced"] = traced_routing(art)
    art["peaks_counted"] = peaks(art)
    per_token = (art["moe"]["moe_rows"] / art["moe"]["tokens"]
                 if art["moe"] else 0.0)
    seq = art["job"]["block_size"]
    art["flops_per_token"] = ssm_share_costs.flops_per_token(d, seq,
                                                             per_token)
    ctx["say"](phase="ssm", moe_plan=art["moe_plan"],
               ssd_plan=art["ssd_plan"], window=art["moe"],
               traced=art["moe_traced"], peaks=art["peaks_counted"],
               first_epoch=first_epoch(art),
               routed_rows_per_token=per_token,
               flops_per_token=art["flops_per_token"],
               forward_flops_per_token=ssm_share_costs
               .forward_flops_per_token(d, seq, per_token),
               scan_least_ms_a_mixer=(
                   1000.0 * ssm_share_costs.scan_least_seconds(
                       d, art["job"]["batch_size"] * seq, art["peaks"])
                   if art["peaks"] else None))
    if art["moe"] and art["moe"]["moe_dropped"]:
        # a dropless layer that lost a pair computed another function
        art["correct"] = False
        art["checks"]["moe_dropped"] = {
            "value": art["moe"]["moe_dropped"], "limit": 0}
    return art
