"""Multi-tenant LoRA adapters: low-rank per-tenant fine-tunes of a shared
base model (arXiv:2106.09685 applied to this serving stack).

The north-star workload — millions of users — implies many tenants wanting
per-tenant behavior without N× copies of the base weights.  A LoRA adapter
is a pair of low-rank factors per targeted Linear projection
(``ΔW = (alpha/r)·B·A``, A: (r, in) and B: (out, r), B zero-initialized so
a fresh adapter is exactly the base model), a few-hundred-KB artifact per
tenant against a multi-GB base.

Two application modes, both implemented in ``ops/modules.Linear``:

- **Bound** (one adapter, whole batch): :func:`bind_model` merges
  ``<prefix>.lora_A/B/scale`` keys into the flat param dict, and every
  existing compiled program (legacy generate, one-shot prefill, the
  training forward) picks the delta up through the ordinary
  ``Ctx.params`` path — no new program families.
- **Stacked** (mixed adapters, one shared decode batch): :func:`build_pack`
  stacks up to ``PENROZ_LORA_MAX_LIVE`` live adapters into static
  ``[L+1, R, ·]`` tensors (rank-padded to ``PENROZ_LORA_MAX_RANK``, the
  trailing slot all-zero for base rows) and a per-row slot-index vector
  gathers each row's factors inside the forward (BGMV-style einsum) — rows
  with different adapters (or none) share ONE decode step.  Static shapes
  keep the compiled-program set bounded: the program retraces only when
  the set of targeted projections changes, never per adapter.

Training (:func:`train_adapter`) freezes the base params — gradients flow
only into the adapter tree (``jax.value_and_grad`` over argument 0; the
parameter-subset analog of the pjit training recipe in PAPERS.md) — and
writes an adapter-only checkpoint (utils/checkpoint.py container, CRC32
streams) loadable straight into the serving registry
(serve/adapters.py).

Knobs::

    PENROZ_LORA_MAX_LIVE   adapters stacked per engine batch (default 4)
    PENROZ_LORA_MAX_RANK   rank ceiling / stack padding (default 16)
"""

from __future__ import annotations

import copy
import logging
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from penroz_tpu.ops import modules as M
from penroz_tpu.utils import checkpoint

log = logging.getLogger(__name__)

MAX_LIVE_ENV = "PENROZ_LORA_MAX_LIVE"
MAX_RANK_ENV = "PENROZ_LORA_MAX_RANK"


def _env_int(name: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(name, str(default))))
    except ValueError:
        log.warning("Unparseable %s=%r; using default %d", name,
                    os.environ.get(name), default)
        return default


def max_live() -> int:
    """Adapters stackable into one engine batch (``PENROZ_LORA_MAX_LIVE``)."""
    return _env_int(MAX_LIVE_ENV, 4)


def max_rank() -> int:
    """Rank ceiling and stack padding width (``PENROZ_LORA_MAX_RANK``)."""
    return _env_int(MAX_RANK_ENV, 16)


def validate_config(config: dict) -> dict:
    """Normalize an adapter config dict ``{rank, alpha, targets}``;
    ValueError (→ HTTP 400) on a rank outside [1, PENROZ_LORA_MAX_RANK]."""
    rank = int(config.get("rank", 8))
    if rank < 1 or rank > max_rank():
        raise ValueError(
            f"adapter rank {rank} outside [1, {max_rank()}] "
            f"(raise {MAX_RANK_ENV} to allow larger ranks)")
    alpha = config.get("alpha")
    alpha = float(alpha) if alpha is not None else 2.0 * rank
    targets = config.get("targets") or None
    if targets is not None:
        targets = [str(t) for t in targets]
    return {"rank": rank, "alpha": alpha, "targets": targets}


def scale(config: dict) -> float:
    return float(config["alpha"]) / float(config["rank"])


def target_linears(arch, targets: Optional[list] = None) -> list[tuple]:
    """(prefix, in_features, out_features) of every targeted Linear.

    ``targets`` is a list of substring matchers against the module's flat
    param prefix (``layers.2.0.1`` style); None/empty targets every Linear
    in the stack — attention QKV/output projections and MLP projections
    alike (GatedMLP children are Linears and match through the same walk).
    """
    out = []
    for mod in arch.mods:
        for sub in mod.walk():
            if type(sub) is not M.Linear:
                continue
            if targets and not any(t in sub.prefix for t in targets):
                continue
            out.append((sub.prefix, sub.in_features, sub.out_features))
    if not out:
        raise ValueError(
            f"adapter targets {targets!r} match no Linear projection in "
            f"this model")
    return out


def init_params(arch, config: dict, seed: int = 0,
                init: str = "zeros") -> dict:
    """Fresh adapter tree: A ~ N(0, 1/sqrt(in)) per target, B zeros — a
    new adapter serves as an exact identity until trained.  ``init=
    'random'`` also randomizes B (benchmarks/tests that need a non-trivial
    delta without a training run)."""
    rng = np.random.default_rng(seed)
    r = config["rank"]
    params = {}
    for prefix, din, dout in target_linears(arch, config["targets"]):
        params[f"{prefix}.lora_A"] = (
            rng.standard_normal((r, din)) / np.sqrt(din)).astype(np.float32)
        if init == "random":
            params[f"{prefix}.lora_B"] = (
                rng.standard_normal((dout, r)) / np.sqrt(r)
            ).astype(np.float32)
        else:
            params[f"{prefix}.lora_B"] = np.zeros((dout, r), np.float32)
    return params


def bind_model(model, adapter_params: dict, config: dict):
    """Shallow model copy with the adapter factors bound into the flat
    param dict — every compiled program applies ``base + (alpha/r)·B·A·x``
    for the targeted projections through the ordinary ``Ctx.params`` path
    (jit retraces once per bound structure; the arch's program cache is
    shared with the unbound model)."""
    bound = copy.copy(model)
    extra = {k: jnp.asarray(v) for k, v in adapter_params.items()}
    s = jnp.asarray(scale(config), jnp.float32)
    for key in adapter_params:
        if key.endswith(".lora_A"):
            extra[key[:-len("lora_A")] + "lora_scale"] = s
    bound.params = {**model.params, **extra}
    return bound


def build_pack(slot_params: list, slot_configs: list, n_slots: int) -> dict:
    """Stack per-slot adapter trees into the static mixed-batch pack.

    ``slot_params[i]`` / ``slot_configs[i]`` describe slot ``i`` (None =
    empty slot).  Returns ``{prefix: {a: (n_slots+1, R, in), b: (n_slots+1,
    out, R), scale: (n_slots+1,)}}`` over the UNION of targeted prefixes,
    rank-padded to ``PENROZ_LORA_MAX_RANK`` — zero-padded rows/slots
    contribute an exactly-zero delta, and the trailing slot is the
    always-zero base-row slot.  Returns None when no slot is live.
    """
    R = max_rank()
    shapes: dict = {}
    for params in slot_params:
        if params is None:
            continue
        for key, v in params.items():
            if key.endswith(".lora_A"):
                prefix = key[:-len(".lora_A")]
                b = params[f"{prefix}.lora_B"]
                shapes[prefix] = (v.shape[1], b.shape[0])  # (in, out)
    if not shapes:
        return None
    pack = {}
    for prefix, (din, dout) in shapes.items():
        a = np.zeros((n_slots + 1, R, din), np.float32)
        b = np.zeros((n_slots + 1, dout, R), np.float32)
        s = np.zeros((n_slots + 1,), np.float32)
        for i, (params, cfg) in enumerate(zip(slot_params, slot_configs)):
            if params is None:
                continue
            ak = params.get(f"{prefix}.lora_A")
            if ak is None:  # this slot's adapter doesn't target the prefix
                continue
            r = ak.shape[0]
            a[i, :r] = ak
            b[i, :, :r] = params[f"{prefix}.lora_B"]
            s[i] = scale(cfg)
        pack[prefix] = {"a": jnp.asarray(a), "b": jnp.asarray(b),
                        "scale": jnp.asarray(s)}
    return pack


def merge_weights(base_params: dict, adapter_params: dict,
                  config: dict) -> dict:
    """Base params with every targeted weight replaced by ``W +
    (alpha/r)·B·A`` — the offline-merge oracle used by tests."""
    out = dict(base_params)
    s = scale(config)
    for key, a in adapter_params.items():
        if not key.endswith(".lora_A"):
            continue
        prefix = key[:-len(".lora_A")]
        b = adapter_params[f"{prefix}.lora_B"]
        w = np.asarray(out[f"{prefix}.weight"], np.float32)
        out[f"{prefix}.weight"] = jnp.asarray(
            w + s * (np.asarray(b, np.float32) @ np.asarray(a, np.float32)))
    return out


# ---------------------------------------------------------------------------
# Adapter checkpoints
# ---------------------------------------------------------------------------

def save_adapter(adapter_id: str, model_id: str, config: dict,
                 params: dict, status: dict, progress: list | None = None,
                 sync_flush: bool = False):
    checkpoint.save_adapter(adapter_id, {
        "adapter_id": adapter_id,
        "model_id": model_id,
        "config": config,
        "params": {k: np.asarray(v) for k, v in params.items()},
        "status": status,
        "progress": progress or [],
    }, sync_flush=sync_flush)


def create_adapter(adapter_id: str, model, config: dict, seed: int = 0,
                   init: str = "zeros") -> dict:
    """Initialize + persist a fresh adapter for ``model`` (POST /adapters/
    and the train path's create-on-first-train).  Returns the blob tree."""
    config = validate_config(config)
    params = init_params(model.arch, config, seed=seed, init=init)
    save_adapter(adapter_id, model.model_id, config, params,
                 {"code": "Created", "message": "Adapter created"},
                 sync_flush=True)
    return {"adapter_id": adapter_id, "model_id": model.model_id,
            "config": config, "params": params}


# ---------------------------------------------------------------------------
# Training: freeze the base, descend only the adapter tree
# ---------------------------------------------------------------------------

def train_adapter(model, adapter_id: str, config: dict, dataset_id: str,
                  shard: int = 0, epochs: int = 1, batch_size: int = 1,
                  block_size: int = 1024, step_size: int = 1):
    """API-driven adapter fine-tuning: ``POST /train/`` with an ``adapter``
    config lands here instead of :meth:`NeuralNetworkModel.train_model`.

    The base params are FROZEN — ``value_and_grad`` differentiates only
    the adapter tree, so the optimizer state is adapter-sized (KBs, not
    the base model's moments) and the checkpoint written every ~10 s and
    at completion is adapter-only, loadable straight into the serving
    registry.  Reference loader semantics match the base trainer: every
    micro-step consumes a full ``(batch_size, block_size)`` buffer and
    ``models/model.py::accumulation_steps`` micro-steps accumulate
    into one update.  An existing adapter checkpoint with the same config
    resumes from its params (continued fine-tuning); a config mismatch is
    a ValueError.
    """
    from penroz_tpu.data.loaders import Loader
    from penroz_tpu.models import dsl
    from penroz_tpu.models.model import accumulation_steps
    import optax

    config = validate_config(config)
    model_id = model.model_id
    try:
        existing = checkpoint.load_adapter(adapter_id)
    except KeyError:
        existing = None
    if existing is not None:
        if existing.get("model_id") != model_id:
            raise ValueError(
                f"adapter {adapter_id!r} belongs to model "
                f"{existing.get('model_id')!r}, not {model_id!r}")
        prev = validate_config(existing.get("config") or {})
        if (prev["rank"], prev["targets"]) != (config["rank"],
                                               config["targets"]):
            raise ValueError(
                f"adapter {adapter_id!r} exists with rank="
                f"{prev['rank']} targets={prev['targets']}; retrain with "
                f"the same shape or DELETE /adapters/ first")
        lora_params = {k: jnp.asarray(v)
                       for k, v in existing["params"].items()}
    else:
        lora_params = {k: jnp.asarray(v) for k, v in
                       init_params(model.arch, config).items()}

    arch = model.arch
    progress: list = []

    def persist(status, sync=False):
        save_adapter(adapter_id, model_id, config, lora_params, status,
                     progress, sync_flush=sync)

    persist({"code": "Training",
             "message": f"Training adapter on {dataset_id}"})
    try:
        buffer_size = batch_size * block_size
        num_steps = accumulation_steps(batch_size, step_size)
        loader = Loader(dataset_id, begin_shard=shard, begin_idx=0,
                        buffer_size=buffer_size, idx_offset=buffer_size)
        optimizer = dsl.build_optimizer(model.optimizer_config)
        opt_state = optimizer.init(lora_params)
        platform = model._placement
        s = jnp.asarray(scale(config), jnp.float32)
        scale_keys = {k[:-len("lora_A")] + "lora_scale"
                      for k in lora_params if k.endswith(".lora_A")}

        def loss_fn(lp, base, bufs, x, y, rng):
            params = {**base, **lp}
            for key in scale_keys:
                params[key] = s
            _, cost, _, _ = arch.forward(params, bufs, x, y, training=True,
                                         rng=rng, skip_softmax=True,
                                         platform=platform)
            return cost

        grad_fn = jax.value_and_grad(loss_fn)

        def epoch_fn(lp, opt_st, base, bufs, xs, ys, rng):
            def micro(carry, batch):
                grads_acc, cost_acc, i = carry
                x, y = batch
                cost, grads = grad_fn(lp, base, bufs, x, y,
                                      jax.random.fold_in(rng, i))
                grads_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), grads_acc,
                    grads)
                return (grads_acc, cost_acc + cost, i + 1), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), lp)
            (grads, cost_sum, _), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32), 0), (xs, ys))
            inv = 1.0 / num_steps
            grads = jax.tree.map(lambda g, p: (g * inv).astype(p.dtype),
                                 grads, lp)
            updates, opt_st = optimizer.update(grads, opt_st, lp)
            return optax.apply_updates(lp, updates), opt_st, cost_sum * inv

        fn = jax.jit(epoch_fn, donate_argnums=(0, 1))
        rng = jax.random.key(0)
        last_save = time.monotonic()
        for epoch in range(epochs):
            t0 = time.monotonic()
            xs, ys = [], []
            for _ in range(num_steps):
                x, y = loader.next_batch()
                xs.append(x.reshape(batch_size, block_size))
                ys.append(y.reshape(batch_size, block_size))
            lora_params, opt_state, cost = fn(
                lora_params, opt_state, model.params, model.buffers,
                np.stack(xs), np.stack(ys), jax.random.fold_in(rng, epoch))
            cost = float(cost)
            duration = time.monotonic() - t0
            progress.append({"epoch": epoch + 1, "cost": cost,
                             "durationInSecs": duration})
            log.info("Adapter %s epoch %d: cost=%.4f", adapter_id,
                     epoch + 1, cost)
            if time.monotonic() - last_save >= 10:
                persist({"code": "Training",
                         "message": f"Training adapter on {dataset_id}"})
                last_save = time.monotonic()
        persist({"code": "Trained",
                 "message": f"Trained {epochs} epoch(s)"}, sync=True)
        log.info("Adapter %s training completed (%d epochs)", adapter_id,
                 epochs)
    except Exception as e:  # noqa: BLE001 — record, then surface
        try:
            persist({"code": "Error", "message": str(e)}, sync=True)
        except Exception:  # noqa: BLE001
            log.exception("Failed to persist adapter error status")
        raise
    return lora_params
