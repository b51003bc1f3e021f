"""Model runtime — ``ops/modules.py::MixtureOfExperts`` (dropless): the rows
the grouped products computed beyond the rows routed, over the rows routed,
in the window's epochs: (``moe_rows_padded`` − ``moe_rows``) ÷ ``moe_rows``
of the program's ``penroz/train_epoch`` counters
(``kinds/train_moe_share.py`` sums them).  Every held expert's group is
padded to whole kernel tiles; what a smaller tile or a fuller group would
lower.  A program without the counters gives nothing to read."""


def read(art):
    moe = art.get("moe")
    if not moe or not moe.get("moe_rows"):
        return None
    return 100.0 * (moe["moe_rows_padded"] - moe["moe_rows"]) / moe["moe_rows"]
