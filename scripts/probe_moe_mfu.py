"""On-chip probe: why does capacity MoE dispatch measure ~= dense?

All timing syncs via float() host transfers, as bench.py does.  Phase
order: first reproduce bench.py's headline train number as a sanity
check, then dense-vs-capacity MoE stacks, then capacity dispatch-group
variants.

Writes each result to scripts/probe_results.json as it lands.
Throwaway instrumentation, not part of the framework.
"""
import json
import os
import time

import jax
import numpy as np

OUT = os.path.join(os.path.dirname(__file__), "probe_results.json")
results = {}


def emit(**kv):
    results.update(kv)
    with open(OUT, "w") as fh:
        json.dump(results, fh, indent=1, sort_keys=True)
    print("probe:", kv, flush=True)


def sanity_train():
    from __graft_entry__ import OPTIMIZER, _gpt2_dsl
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import CompiledArch
    import bench as B

    mapper = Mapper(_gpt2_dsl(depth=12, d=768, block=1024, heads=12),
                    OPTIMIZER)
    arch = CompiledArch.get(mapper.layers)
    params, _ = mapper.init_params(arch.mods, seed=0)
    params = jax.device_put(params, jax.devices()[0])
    tps, _ = B.bench_train(arch, mapper, params, batch=8, block=1024,
                           steps_per_call=4, warmup=2, timed=4)
    emit(sanity_headline_tps=round(tps, 1))
    return tps


def moe_variants():
    import bench as B
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import CompiledArch
    from penroz_tpu.ops import modules as M
    from __graft_entry__ import OPTIMIZER

    def run(dispatch, group=None, top_k=2, tag=""):
        if group is not None:
            M.MixtureOfExperts.DISPATCH_GROUP = group
        try:
            # same stack shape as the shipped bench_moe_dispatch
            d, experts, depth, batch, block = 512, 8, 4, 8, 512
            layers = [{"summation": [
                {"embedding": {"num_embeddings": 50304,
                               "embedding_dim": d},
                 "normal": {"mean": 0.0, "std": 0.02}},
                {"position": {"num_embeddings": block, "embedding_dim": d},
                 "normal": {"mean": 0.0, "std": 0.02}}]}]
            layers += [{"residual": [
                {"sequential": [
                    {"layernorm": {"normalized_shape": d}},
                    {"linear": {"in_features": d, "out_features": 3 * d},
                     "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                    {"attention": {"num_heads": 8, "dropout": 0.0}},
                    {"linear": {"in_features": d, "out_features": d}}]},
                {"sequential": [
                    {"layernorm": {"normalized_shape": d}},
                    {"moe": {"in_features": d, "intermediate_size": 4 * d,
                             "num_experts": experts, "top_k": top_k,
                             "dispatch": dispatch}}]}]}
                for _ in range(depth)]
            layers += [{"layernorm": {"normalized_shape": d}},
                       {"linear": {"in_features": d, "out_features": 50304,
                                   "bias": False}},
                       {"softmax": {"dim": -1}}]
            mapper = Mapper(layers, OPTIMIZER)
            arch = CompiledArch.get(mapper.layers)
            params, buffers = mapper.init_params(arch.mods, seed=0)
            tps, _ = B.bench_train(arch, mapper, params, batch=batch,
                                   block=block, steps_per_call=2,
                                   warmup=2, timed=6, buffers=buffers)
            emit(**{f"moe_{tag or dispatch}_tps": round(tps, 1)})
        finally:
            M.MixtureOfExperts.DISPATCH_GROUP = 512

    run("dense")
    run("capacity", group=512, tag="cap_g512")
    run("capacity", group=2048, tag="cap_g2048")
    run("capacity", group=4096, tag="cap_g4096")
    run("capacity", group=512, top_k=1, tag="cap_k1_g512")
    run("dense", top_k=1, tag="dense_k1")


if __name__ == "__main__":
    emit(device=str(jax.devices()[0].device_kind),
         ts=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    sanity_train()
    moe_variants()
