"""Worker body for the REAL two-process multi-host tests.

Launched as a subprocess by ``test_multihost_real.py`` with a scrubbed
environment (``JAX_PLATFORMS=cpu``, two virtual CPU devices per process) and the standard multi-host env knobs
(``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``)
— the same wiring a TPU pod uses, so ``dist.initialize()`` takes the
production path and every collective (gradient psum over the global mesh,
``all_reduce_mean`` of the eval cost, shard-file checkpointing) runs for
real across OS processes rather than being mocked.
"""

import json
import os
import sys


def main():
    cfg = json.loads(sys.argv[1])
    os.chdir(cfg["workdir"])
    from penroz_tpu.utils import checkpoint
    checkpoint.SHM_PATH = os.path.join(cfg["workdir"], "shm")
    os.makedirs(checkpoint.SHM_PATH, exist_ok=True)

    from penroz_tpu.parallel import dist
    assert dist.initialize(), "JAX_* multi-host env vars not picked up"

    import numpy as np
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import NeuralNetworkModel

    model = NeuralNetworkModel(cfg["model_id"],
                               Mapper(cfg["layers"], cfg["optimizer"]))
    model.to_device("cpu")
    model.train_model(cfg["dataset"], shard=0, epochs=cfg["epochs"],
                      batch_size=cfg["batch_size"],
                      block_size=cfg["block_size"],
                      step_size=cfg["step_size"])
    rank = dist.process_index()
    cost = model.evaluate_model(cfg["dataset"], None, 0, 1,
                                cfg["batch_size"], cfg["block_size"],
                                cfg["step_size"])
    dump = {"cost": np.float32(cost)}
    for k, v in model.params.items():
        if (getattr(v, "is_fully_addressable", True)
                or getattr(v, "is_fully_replicated", False)):
            dump[k.replace("/", "_")] = np.asarray(v, np.float32)
    np.savez(os.path.join(cfg["workdir"], f"proc{rank}.npz"), **dump)
    print(f"worker {rank} done status={model.status['code']}", flush=True)
    assert model.status["code"] == "Trained", model.status


if __name__ == "__main__":
    main()
