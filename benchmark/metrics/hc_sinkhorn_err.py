"""Model runtime — ``ops/modules.py::HyperConnected``: the largest |column
sum − 1| of the multi-stream residual's mixing matrix H_res after its last
Sinkhorn iteration, over the tokens, sub-blocks and micro-steps of the
window's epochs: the largest ``hc_sinkhorn_err`` of the program's
``penroz/train_epoch`` counters (``kinds/train_mla_share.py::peaks``).  Rows
end at 1 by construction; the columns say whether the iterations converged
on the weights the job has reached.  A program without the counter gives
nothing to read."""


def read(art):
    peaks = art.get("peaks_counted")
    if not peaks or "hc_sinkhorn_err" not in peaks:
        return None
    return peaks["hc_sinkhorn_err"]
