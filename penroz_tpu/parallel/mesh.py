"""Device meshes and sharding rules.

All parallelism is expressed as shardings over a ``jax.sharding.Mesh`` and
compiled by XLA into ICI/DCN collectives — there is no wrapper object doing
gradient allreduce (the reference's DistributedDataParallel + NCCL buckets,
neural_net_model.py:609, ddp.py:80-85).  Axes:

- ``data``      — batch sharding (DP); gradients are averaged by XLA because
                  replicated params + sharded batch force a psum.
- ``model``     — tensor parallelism for weight matrices (TP).
- ``sequence``  — context/sequence parallelism for long sequences (SP).
- ``expert``    — expert parallelism for MoE layers (EP): stacked expert
                  weights shard their leading E dim; the top-k combine is a
                  contraction over E that XLA lowers to a psum on the axis.
- ``pipe``      — pipeline parallelism (PP): stacked transformer-block
                  params shard their leading layer dim; microbatches stream
                  between stages via ppermute (parallel/pipeline.py).

Single-device training uses a trivial 1-device mesh so the code path is
identical everywhere.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger(__name__)

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "sequence"
EXPERT_AXIS = "expert"
PIPE_AXIS = "pipe"


def make_mesh(devices=None, *, data: Optional[int] = None, model: int = 1,
              sequence: int = 1, expert: int = 1, pipe: int = 1,
              pipe_outermost: bool = False) -> Mesh:
    """Build a (data, model, sequence, expert, pipe) mesh over the given
    (default: all) devices.  ``data`` defaults to whatever is left over.

    ``pipe_outermost=True`` makes ``pipe`` the slowest-varying axis of the
    device assignment: stage ``s`` occupies the contiguous global device
    range ``[s·n/P, (s+1)·n/P)``.  ``jax.devices()`` orders devices by
    process, so under multi-host this maps each pipeline stage onto a
    contiguous group of hosts — the stage handoff (``ppermute``) crosses
    DCN once per tick while the within-stage axes stay on ICI.  The
    default (pipe fastest-varying) keeps whole pipelines inside a host:
    right when PP is used for schedule overlap rather than to fit a model
    across hosts.  Axis *names* are identical either way; only the
    device→coordinate assignment differs.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    denom = model * sequence * expert * pipe
    if data is None:
        if n % denom != 0:
            raise ValueError(f"{n} devices not divisible by model={model} × "
                             f"sequence={sequence} × expert={expert} × "
                             f"pipe={pipe}")
        data = n // denom
    if data * denom != n:
        raise ValueError(f"mesh {data}×{model}×{sequence}×{expert}×{pipe} "
                         f"!= {n} devices")
    if pipe_outermost:
        arr = np.moveaxis(
            np.array(devices).reshape(pipe, data, model, sequence, expert),
            0, -1)
    else:
        arr = np.array(devices).reshape(data, model, sequence, expert, pipe)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS, SEQ_AXIS, EXPERT_AXIS,
                      PIPE_AXIS))


def _replica_devices(devices, width: int, replica: int) -> list:
    """The ``width`` local devices replica ``replica`` of a router group
    owns: the contiguous range ``[replica·width, (replica+1)·width)``, so
    N replicas on an N·width-chip host each get their own chips.  A host
    too small for that range collapses the replica onto the first
    ``width`` devices — same programs and numerics, shared placement (the
    CPU parity suite rides this) — and says so, since on real chips it
    means replicas queue behind one another on the same device."""
    lo = int(replica) * width
    if lo and lo + width > len(devices):
        log.warning("serving replica %d needs local devices [%d, %d) but "
                    "the host has %d; sharing the first %d with replica 0",
                    replica, lo, lo + width, len(devices), width)
        lo = 0
    return devices[lo:lo + width]


def serve_mesh(model: int = 1, devices=None, replica: int = 0) -> Mesh:
    """Serving mesh for ONE decode engine: ``model`` tensor-parallel
    devices, every other axis trivial.  Data parallelism across engines is
    the router's job (serve/router.py) — replicas own disjoint meshes
    (:func:`_replica_devices`) rather than sharing a ``data`` axis, so one
    replica's crash recovery never invalidates another's compiled
    programs.  Replica 0 is built over the FIRST ``model`` local devices,
    so a 1-wide mesh on a multi-device host stays on device 0 exactly like
    the unmeshed engine (the token-parity guarantee the CPU suite proves
    rides on this)."""
    devices = list(devices if devices is not None else jax.local_devices())
    if model < 1 or model > len(devices):
        raise ValueError(f"serve mesh needs 1 <= model <= {len(devices)} "
                         f"local devices (got model={model})")
    return make_mesh(_replica_devices(devices, model, replica), model=model)


def serve_stage_meshes(stages: int, model: int = 1,
                       devices=None, replica: int = 0) -> list[Mesh]:
    """Per-stage serving meshes for ONE pipeline group
    (PENROZ_SERVE_PIPE_STAGES × PENROZ_SERVE_MESH_MODEL): stage ``s``
    owns the contiguous device range ``[s·model, (s+1)·model)`` of its
    replica's ``stages × model`` devices as its own ``model``-wide TP
    mesh.  Disjoint meshes rather than one
    ``pipe``-axis mesh because serving stages are MPMD — each stage
    compiles and dispatches its own program and the scheduler hands
    activations across (PAPERS.md #3), so a stage recompile or crash
    never invalidates a sibling's programs (same isolation argument as
    router replicas).  When the host has fewer than ``stages × model``
    devices every stage collapses onto the first ``model`` devices —
    placement degenerates but the schedule, partition, and numerics are
    identical (the CPU parity suite rides this)."""
    devices = list(devices if devices is not None else jax.local_devices())
    stages = int(stages)
    if stages < 1 or model < 1:
        raise ValueError(f"need stages >= 1 and model >= 1 "
                         f"(got {stages}, {model})")
    if len(devices) < stages * model:
        return [serve_mesh(model=model, devices=devices)] * stages
    devices = _replica_devices(devices, stages * model, replica)
    return [make_mesh(devices[s * model:(s + 1) * model], model=model)
            for s in range(stages)]


def batch_sharding(mesh: Mesh, batch_ndim: int = 2) -> NamedSharding:
    """Shard the leading batch dim over ``data``.  For sequence sharding use
    ``parallel.sharding.shard_batch`` (spec-based, handles both axes)."""
    spec = [DATA_AXIS] + [None] * (batch_ndim - 1)
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_data_size(mesh: Mesh) -> int:
    """Number of devices along the data axis."""
    return mesh.shape[DATA_AXIS]
