"""What the benchmark takes from the program, in one place: creating a model
with weights the benchmark made, and letting go of it afterwards."""

from __future__ import annotations

import gc
import importlib


def reference_for(cfg: dict):
    """``benchmark/reference/<name>.py`` of the configuration: the plain
    implementation, and the adapter that names the program's preset."""
    return importlib.import_module("benchmark.reference." + cfg["reference"])


def create_model(cfg: dict, seed: int, model_id: str) -> dict:
    """The two calls ``POST /model/`` makes (construct, serialize), made
    here with the weights replaced in between: the route has no way to
    supply weights, and the reference may use nothing the program made.
    Weights come from the seed in one jitted call on the default device, in
    float32, the type ``/model/`` creates and serves them in."""
    from penroz_tpu.models import presets
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel
    from penroz_tpu.utils import checkpoint
    ref = reference_for(cfg)
    args = ref.preset_args(cfg)
    layers = getattr(presets, ref.PRESET)(**args)
    model = NeuralNetworkModel(model_id, Mapper(layers, cfg["optimizer"]))
    shapes = {k: (tuple(v.shape), v.dtype) for k, v in model.params.items()}
    model.params = {}
    weights = ref.init_program_weights(cfg, seed)
    got = {k: (tuple(v.shape), v.dtype) for k, v in weights.items()}
    if got != shapes:
        odd = sorted(k for k in set(got) | set(shapes)
                     if got.get(k) != shapes.get(k))
        raise ValueError(f"the reference's weights do not fit the program's "
                         f"{ref.PRESET}: {odd[:6]}")
    model.params = dict(weights)
    n_params = sum(int(v.size) for v in weights.values())
    model.serialize()
    checkpoint.join_flushes(timeout=600)
    del model, weights
    gc.collect()
    return {"layers": layers, "n_params": n_params}


def delete_model(svc, model_id: str):
    from penroz_tpu.utils import checkpoint
    svc.call("DELETE", "/model/?model_id=" + model_id)
    checkpoint.join_flushes(timeout=600)


def device_memory(devices) -> dict:
    """``memory_stats()`` of the fullest of ``devices``: bytes in live
    buffers now and at their peak, and the scratch space reserved for
    running programs now and at its peak (``None`` where the backend reports
    none).  The TPU runtime keeps the two apart: ``peak_bytes_in_use`` alone
    leaves out a running program's temporaries (7.9 GB of the 10.2 GB a
    GPT-2 124M training step holds, my chip run, PR 24)."""
    stats = [d.memory_stats() or {} for d in devices]
    pick = lambda key: max((s[key] for s in stats if s.get(key) is not None),
                           default=None)
    return {key: pick(key) for key in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved")}


def memory_peak_bytes(samples: list) -> int | None:
    """The most the fullest chip held, from readings of
    :func:`device_memory` taken while the cell's work ran: the peak of live
    buffers, or live buffers plus program scratch as read together in one
    sample, whichever is larger."""
    peaks = [s["peak_bytes_in_use"] for s in samples
             if s.get("peak_bytes_in_use") is not None]
    if not peaks:
        return None
    held = max((s["bytes_in_use"] or 0) + (s.get("bytes_reserved") or 0)
               for s in samples)
    return max(max(peaks), held)
