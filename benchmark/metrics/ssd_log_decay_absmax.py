"""Model runtime — ``ops/modules.py::Mamba2Mixer``: the largest |Σ Δ·A| over
one chunk of the state-space scan, over the heads, chunks, layers and
micro-steps of the window's epochs: the largest ``ssd_log_decay_absmax`` of
the program's ``penroz/train_epoch`` counters
(``kinds/train_ssm_share.py::peaks``).  A chunk's whole decay is the
exponential of minus this: near 87 a float32 ``exp`` underflows and a chunk
forgets what entered it by construction, not by the weights' choice.  A
program without the counter gives nothing to read."""


def read(art):
    peaks = art.get("peaks_counted")
    if not peaks or "ssd_log_decay_absmax" not in peaks:
        return None
    return peaks["ssd_log_decay_absmax"]
