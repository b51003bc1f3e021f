"""Model runtime — ``ops/modules.py::MixtureOfExperts`` (dropless): the
fullest held expert's rows over the mean held expert's, in the window's
epochs: ``moe_load_max`` (the per-layer maxima, summed) × experts held ÷
``moe_rows`` of the program's ``penroz/train_epoch`` counters.  1 under a
uniform router.  It is the traffic's and the weights', not the program's:
recorded so that a step time can be read against it.  A program without the
counters gives nothing to read."""


def read(art):
    moe, plan = art.get("moe"), art.get("moe_plan")
    if not moe or not plan or not moe.get("moe_rows"):
        return None
    return moe["moe_load_max"] * plan["held"] / moe["moe_rows"]
