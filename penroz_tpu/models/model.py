"""Model runtime: compiled architectures and the NeuralNetworkModel facade.

TPU-native re-design of the reference's ``neural_net_model.py``:

- ``CompiledArch`` — a layer DSL compiled once into a bound functional module
  tree with cached jitted programs: forward (all intermediate activations +
  CE/MSE cost, reference :250-271), a grad-accumulating train epoch
  (reference :552-722 hot loop → one ``lax.scan`` under ``jax.jit``), fused
  decode+sample steps over a preallocated KV cache (reference :360-406), and
  an instrumented stats pass (reference :735-777) using an explicit
  activation-delta VJP instead of ``retain_grad``.
- ``NeuralNetworkModel`` — create/train/evaluate/generate/serialize/
  deserialize/delete/from_huggingface lifecycle with the same progress/
  avg-cost/stats/status bookkeeping and /dev/shm write-through checkpoints
  (reference :98-174, 516-722).

Decode is chunked and pipelined: up to ``PENROZ_DECODE_CHUNK`` (default 128)
fused decode+sample steps run per dispatch via ``lax.scan`` with power-of-two
chunk sizes (tails round up to the compiled ceiling and discard the
overshoot), and the next chunk is dispatched before the previous chunk's
tokens are transferred to the host (the last sampled token stays on-device),
bounding per-token dispatch overhead, compile variants, and host round-trips.
"""

from __future__ import annotations

import atexit
import contextlib
import json
import logging
import math
import os
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from penroz_tpu.models import dsl
from penroz_tpu.models.dsl import Mapper
from penroz_tpu.ops import attention as attn_ops
from penroz_tpu.ops import kv_cache as KV
from penroz_tpu.ops import losses
from penroz_tpu.ops import modules as M
from penroz_tpu.parallel import dist
from penroz_tpu.parallel import mesh as mesh_lib
from penroz_tpu.parallel import sharding as sharding_lib
from penroz_tpu.utils import checkpoint, stats as stats_lib, tracing

log = logging.getLogger(__name__)

DECODE_CHUNK_ENV = "PENROZ_DECODE_CHUNK"

# Per-model count of /train/ requests this process has started — feeds the
# train-end barrier id, so it must advance in lockstep on every host and
# survive the per-request model deserialization (see train_model).
_TRAIN_SEQ: dict = {}

# Decode-priority dispatch: /generate/ handlers wrap their device work in
# decode_priority(); the training loop consults decode_pending() between
# epochs and briefly yields the chip so queued decodes slip in ahead of
# the next epoch program (the reference sidesteps the contention by
# forking training into separate processes/devices, main.py:461-464).
_DECODE_PENDING = 0
_DECODE_LOCK = threading.Lock()

# Live training-worker subprocesses by model id (PENROZ_TRAIN_WORKER=1):
# observability + test hook; entries removed as workers exit.  The atexit
# sweep covers clean parent shutdown; the worker also self-terminates on
# parent death (train_worker._watch_parent) so a SIGKILLed server never
# leaves an orphan racing checkpoint writes against its replacement.
_TRAIN_WORKERS: dict = {}


def _kill_train_workers():
    for proc in list(_TRAIN_WORKERS.values()):
        if proc.poll() is None:
            proc.kill()


atexit.register(_kill_train_workers)


@contextlib.contextmanager
def decode_priority():
    """Mark a decode request in flight for the duration of its device work."""
    global _DECODE_PENDING
    with _DECODE_LOCK:
        _DECODE_PENDING += 1
    try:
        yield
    finally:
        with _DECODE_LOCK:
            _DECODE_PENDING -= 1


def decode_pending() -> int:
    return _DECODE_PENDING


def _yield_to_decodes():
    """Between-epoch decode-priority window (single-process only: a
    one-sided pause under a multi-host mesh would just stall the peers'
    collectives).  Caps at PENROZ_DECODE_PRIORITY_MS (default 1000; 0
    disables) so a decode storm cannot starve training."""
    if dist.process_count() > 1:
        return
    cap_ms = float(os.environ.get("PENROZ_DECODE_PRIORITY_MS", "1000"))
    if cap_ms <= 0 or decode_pending() <= 0:
        return
    deadline = time.monotonic() + cap_ms / 1000.0
    while decode_pending() > 0 and time.monotonic() < deadline:
        time.sleep(0.005)


def accumulation_steps(batch_size: int, step_size, world: int = 1) -> int:
    """Micro-steps an optimizer step accumulates: ``batch_size // (step_size
    · world)``, at least 1 (reference: neural_net_model.py:581-586).  Every
    micro-step is a full ``(batch_size, block_size)`` buffer, so a whole
    ``step_size`` can only ask for as many micro-steps as the batch has
    rows; a fraction asks for more (``batch_size`` 1, ``step_size`` 0.25:
    four micro-steps of one row), which is how a job whose chip holds one
    sequence accumulates several."""
    if not step_size > 0:
        raise ValueError(f"step_size must be positive, got {step_size}")
    # floored as the reference floors; the 1e-9 is for fractions such as
    # 0.1, whose quotient falls a rounding short of the whole number
    return max(1, int(batch_size / (step_size * world) + 1e-9))


def _sharded_zero_grads(params: dict) -> dict:
    """fp32 zero-gradient tree laid out like ``params`` — shard-local
    allocation via ``make_array_from_callback``, so a ZeRO-3/TP-sharded
    model never materializes its full unsharded gradient tree on one
    device (the fused epoch's zeros are born inside jit under GSPMD;
    this is the eager-side equivalent for the micro-step driver)."""
    out = {}
    for k, v in params.items():
        sharding = getattr(v, "sharding", None)
        if sharding is None:
            out[k] = jnp.zeros(v.shape, jnp.float32)
            continue

        def shard_zeros(idx, shape=v.shape):
            dims = tuple((sl.stop if sl.stop is not None else d)
                         - (sl.start or 0) for sl, d in zip(idx, shape))
            return np.zeros(dims, np.float32)

        out[k] = jax.make_array_from_callback(v.shape, sharding,
                                              shard_zeros)
    return out


def _check_pipe_composition(pipe: int, seq: int) -> None:
    """The GPipe schedule composes with data parallelism (its microbatch
    spec shards rows over ``data``), with tensor parallelism, AND with
    expert parallelism: stacked leaves carry P(pipe, <tp/ep>, …) specs and
    the stage body leaves the model/expert axes GSPMD-automatic, so XLA
    inserts the TP collectives and the MoE dispatch/combine psums inside
    each stage (EP×pipe parity: costs and router fractions match the
    sequential run to fp tolerance — test_train_model_pipe_composes_with_
    expert_parallel) — and with sequence parallelism in BOTH modes: the
    schedule's shard_map binds the sequence axis as a manual axis and the
    attention modules run the ring or Ulysses body on it directly
    (Ctx.sp_manual_axis; their shard_map wrappers cannot nest, the manual
    entry points skip them).  Every mesh axis now composes with pipe;
    the per-model constraints (attention dropout, bf16 storage) are
    validated at layout entry.  Kept as the shared seam between the
    single- and multi-host mesh builders."""
    del pipe, seq  # every composition valid at mesh level


def _chunk_budget() -> int:
    """Decode steps fused per dispatch (PENROZ_DECODE_CHUNK, default 128)."""
    return max(1, int(os.environ.get(DECODE_CHUNK_ENV, "128")))


def _decode_chunk_size(remaining: int, cap: int) -> int:
    """Pow-2 ceiling of the remaining tail, clipped by ``cap`` (a non-pow-2
    cap floors back down) — the bounded-program-set chunk policy shared by
    the single-sequence and batched decode loops."""
    chunk = min(1 << (remaining - 1).bit_length(), cap)
    if chunk & (chunk - 1):
        chunk = 1 << (chunk.bit_length() - 1)
    return chunk


def _max_generate_batch() -> int:
    """Server-side /generate_batch/ row cap (PENROZ_MAX_GENERATE_BATCH)."""
    try:
        return max(1, int(os.environ.get("PENROZ_MAX_GENERATE_BATCH", "64")))
    except ValueError:
        log.warning("Unparseable PENROZ_MAX_GENERATE_BATCH=%r; "
                    "using default 64",
                    os.environ.get("PENROZ_MAX_GENERATE_BATCH"))
        return 64


def validate_batch_generation(prompts: list[list[int]], block_size: int,
                              max_new_tokens: int) -> None:
    """Reject batched-generation requests the ragged path cannot serve
    losslessly: the batched decode has no overflow crop/re-prefill, so any
    row with ``prompt_len + max_new_tokens > block_size`` would be silently
    truncated — name the offending rows in a ValueError (HTTP 400) instead.
    Shared by ``generate_tokens_batched`` and the continuous-batching route
    so both surfaces enforce identical contracts."""
    if not prompts or any(not p for p in prompts):
        raise ValueError("each batched prompt needs at least one token")
    max_batch = _max_generate_batch()
    if len(prompts) > max_batch:
        raise ValueError(
            f"batched generation accepts at most {max_batch} prompts "
            f"(got {len(prompts)}; raise PENROZ_MAX_GENERATE_BATCH to "
            f"override) — each row allocates a block_size KV cache per "
            f"layer")
    over = [(i, len(p)) for i, p in enumerate(prompts)
            if len(p) + max_new_tokens > block_size]
    if over:
        detail = ", ".join(f"row {i} (prompt {n} tokens)"
                           for i, n in over[:8])
        more = f" and {len(over) - 8} more" if len(over) > 8 else ""
        raise ValueError(
            f"batched generation needs prompt_len + max_new_tokens "
            f"({max_new_tokens}) <= block_size ({block_size}) for every "
            f"row; overflowing: {detail}{more} — the batched path has no "
            f"overflow crop/re-prefill, so these rows would be silently "
            f"truncated; crop prompts first")


def _resolve_device(device: Optional[str]):
    """Map an API device string to a jax.Device (None = leave placement).

    Unknown strings, and an accelerator this process does not have, raise
    ValueError (→ HTTP 400) — falling back to another device would train
    on the CPU for a typo like ``"tpuu"``, or for ``"tpu"`` in a process
    whose parent holds the chip, and still report ``Trained``.  ``"tpu"``
    means a TPU; ``"cuda"``/``"gpu"``/``"accelerator"`` (reference
    compatibility) mean whichever accelerator is attached."""
    if device is None:
        return None
    device = device.lower()
    # local_devices, not devices: under multi-host the global list leads
    # with process 0's devices, and device_put onto another process's
    # device is an error ("Cannot copy array to non-addressable device").
    if device == "cpu":
        return jax.local_devices(backend="cpu")[0]
    if device not in ("tpu", "cuda", "gpu", "accelerator"):
        raise ValueError(f"Unknown device {device!r}; expected 'cpu', "
                         f"'tpu', 'gpu', 'cuda' or 'accelerator'")
    for backend in (("tpu",) if device == "tpu" else ("tpu", "gpu")):
        try:
            return jax.local_devices(backend=backend)[0]
        except RuntimeError:
            continue
    raise ValueError(
        f"device {device!r} requested but this process has no such "
        f"accelerator (JAX backend here: {jax.default_backend()!r}); "
        f"use 'cpu' to place the model on the host")


class CompiledArch:
    """A layer DSL compiled once; jitted programs cached per configuration.

    Shared across model instances with the same DSL (the reference rebuilds
    module trees per request; here jit caches amortize across requests).
    """

    _cache: dict[str, "CompiledArch"] = {}

    @classmethod
    def get(cls, layers: list[dict]) -> "CompiledArch":
        key = json.dumps(layers, sort_keys=True, default=str)
        arch = cls._cache.get(key)
        if arch is None:
            arch = cls._cache[key] = cls(layers)
        return arch

    def __init__(self, layers: list[dict]):
        self.layers_dsl = layers
        self.mods = dsl.build_modules(layers)
        self.algos = [dsl.layer_algo(entry) for entry in layers]
        self.classification = any(isinstance(m, M.Softmax) for m in self.mods)
        # A stack with several exits (ops/modules.py::Looped): its loss is
        # taken over the exit distribution, not from one pre-softmax
        # activation.
        looped = [m for top in self.mods for m in top.walk()
                  if isinstance(m, M.Looped)]
        if len(looped) > 1 or (looped and looped[0] not in self.mods):
            raise ValueError("a model holds at most one looped stack, as a "
                             "top-level layer")
        self.looped: Optional[M.Looped] = looped[0] if looped else None
        # a multi-stream residual's sub-blocks, the latent-attention layers
        walked = [m for top in self.mods for m in top.walk()]
        self.hyper = [m for m in walked if isinstance(m, M.HyperConnected)]
        self.latent = [m for m in walked if isinstance(m, M.LatentAttention)]
        self.mixers = [m for m in walked if isinstance(m, M.Mamba2Mixer)]
        self.param_order: list[str] = []
        # What the modules declare to report of a training call, by name
        # (ops/modules.py::Stat): a training epoch folds it over its
        # micro-steps and returns it after its other results.
        self.step_stats: dict[str, M.Stat] = {}
        for sub in walked:
            for name in sub.param_shapes():
                self.param_order.append(sub.key(name))
            for stat in sub.stats():
                if (self.step_stats.setdefault(stat.name, stat) != stat
                        or stat.reduce not in ("mean", "sum", "max")
                        or stat.family not in tracing.TRAIN_FAMILIES):
                    raise ValueError(
                        f"{type(sub).__name__} declares {stat}: a mean, sum "
                        f"or max of a family of utils/tracing.py, and one "
                        f"declaration a name ({self.step_stats[stat.name]})")
        self.attn_layers: list[M.CausalSelfAttention] = []
        self.ssm_layers: list[M.GatedSSM] = []
        self._index_attention()
        self._jit_cache: dict = {}

    # -- structure ----------------------------------------------------------

    def _index_attention(self):
        """Assign KV-cache slots and infer head dims from the preceding fused
        QKV projection (reference derives head dim the same way:
        neural_net_layers.py:61-75).  ``ssm`` blocks get their own slot
        sequence — their state lives in the recurrent child of the KV
        pytree, indexed independently of the attention pools."""

        def visit(mod):
            if isinstance(mod, M.Looped):
                # every (pass, layer) has its own cache slot: the body's
                # layers take the first pass's, and each later pass the
                # same layers ``slots_per_pass`` further on
                first = len(self.attn_layers)
                for _, child in mod.children():
                    visit(child)
                if any(isinstance(m, M.GatedSSM) for m in mod.walk()):
                    raise ValueError("ssm layers inside a looped stack are "
                                     "not supported")
                body = self.attn_layers[first:]
                mod.slots_per_pass = len(body)
                self.attn_layers.extend(body * (mod.steps - 1))
                return
            if isinstance(mod, M.CausalSelfAttention):
                mod.layer_idx = len(self.attn_layers)
                self.attn_layers.append(mod)
            if isinstance(mod, M.GatedSSM):
                mod.layer_idx = len(self.ssm_layers)
                self.ssm_layers.append(mod)
            if isinstance(mod, M.Sequential):
                prev = None
                for child in mod.layers:
                    if (isinstance(child, M.CausalSelfAttention)
                            and child.head_dim is None
                            and isinstance(prev, M.Linear)):
                        child.head_dim = prev.out_features // (
                            child.num_heads + 2 * child.num_kv_heads)
                    visit(child)
                    prev = child
            else:
                for _, child in mod.children():
                    visit(child)

        for mod in self.mods:
            visit(mod)

    @property
    def kv_specs(self) -> list[tuple[int, int]]:
        """Per-attention-layer (num_kv_heads, head_dim) for KV allocation
        (a looped stack: one entry per (pass, layer))."""
        if self.looped is not None and KV.paged_enabled():
            self.refuse_looped("the paged KV pool (PAGED_KV_CACHE=1)")
        self.refuse_latent("a KV cache (/generate/, the paged pool, the "
                           "decode scheduler)")
        self.refuse_mixer("a KV cache (/generate/, the paged pool, the "
                          "decode scheduler)")
        specs = []
        for mod in self.attn_layers:
            if mod.head_dim is None:
                raise ValueError("Attention head_dim could not be inferred; "
                                 "precede attention with a fused QKV linear "
                                 "or pass head_dim explicitly")
            specs.append((mod.num_kv_heads, mod.head_dim))
        return specs

    def refuse_looped(self, what: str):
        """The one error for what a looped stack does not run."""
        if self.looped is not None:
            raise ValueError(
                f"a looped model does not run with {what}: it serves "
                "through the dense KV cache (one slot per pass and layer) "
                "and trains and serves without pipeline stages")

    def refuse_latent(self, what: str):
        """The one error for what latent attention does not run."""
        if self.latent:
            raise ValueError(
                f"a model with latent attention does not run with {what}: "
                "only the expanded form is written (training, /evaluate/, "
                "/output/); the latent cache and the absorbed decode path "
                "are not")

    def refuse_mixer(self, what: str):
        """The one error for what a Mamba-2 mixer does not run."""
        if self.mixers:
            raise ValueError(
                f"a model with a Mamba-2 mixer (mamba2) does not run with "
                f"{what}: the mixer trains and runs uncached (/train/, "
                "/evaluate/, /output/); its convolution and recurrent state "
                "in a cache are not written")

    def end_step(self, buffers: dict) -> dict:
        """``buffers`` after what the modules do once an optimizer step
        (``Module.end_step``: a router's selection bias moves by its
        balance rule)."""
        for top in self.mods:
            for mod in top.walk():
                buffers = {**buffers, **mod.end_step(buffers)}
        return buffers

    @property
    def ssm_specs(self) -> list[tuple[int, int, int]]:
        """Per-``ssm``-layer (num_heads, head_dim, value_dim) for the
        fixed-size recurrent state (ops/ssm.py::SSMState.create)."""
        return [(mod.num_heads, mod.head_dim, mod.value_dim)
                for mod in self.ssm_layers]

    def jit_program_counts(self) -> dict[str, int]:
        """Live jitted-program count per function family — cache keys are
        tuples whose first element names the family (``"sched_step"``,
        ``"mixed_step"``, …).  The ``penroz_jit_programs`` gauge reads
        this at scrape time: shape bucketing exists to keep these counts
        bounded, and the gauge is where churn becomes visible."""
        counts: dict[str, int] = {}
        for key in self._jit_cache:
            fam = key[0] if isinstance(key, tuple) and key else str(key)
            counts[str(fam)] = counts.get(str(fam), 0) + 1
        return counts

    # -- forward ------------------------------------------------------------

    def _apply(self, params, buffers, x, *, training=False, rng=None, kv=None,
               pos_offset=None, skip_softmax=False, compute_dtype=None,
               sp_mesh=None, platform=None, sp_mode="ring", ep_mesh=None,
               lora=None, lora_idx=None, ragged_descs=None, ragged_rows=None,
               targets=None):
        ctx = M.Ctx(params, buffers, training=training, rng=rng, kv=kv,
                    pos_offset=pos_offset, compute_dtype=compute_dtype,
                    sp_mesh=sp_mesh, platform=platform, sp_mode=sp_mode,
                    ep_mesh=ep_mesh, lora=lora, lora_idx=lora_idx,
                    ragged_descs=ragged_descs, ragged_rows=ragged_rows,
                    targets=targets if self.looped is not None else None)
        if self.hyper:
            M.record_hc_plan(self.hyper, x, training, platform, jnp.dtype(
                compute_dtype or jnp.float32).itemsize)
        acts = []
        h = x
        logits = None
        for mod in self.mods:
            if isinstance(mod, M.Softmax):
                if logits is None:
                    logits = h  # pre-softmax activation feeds the CE cost
                if skip_softmax:
                    continue
            h = mod.apply(h, ctx)
            acts.append(h)
        if logits is None:
            logits = h
        return acts, logits, ctx

    def _cost_from_logits(self, logits, targets, platform=None):
        """CE for classification stacks, MSE otherwise (reference forward
        cost semantics: neural_net_model.py:250-271).

        CE streams chunks through a fused custom-VJP loss (Pallas kernels on
        TPU) instead of upcasting the full (B, T, V) logits to fp32
        (ops/losses.py)."""
        if self.classification:
            return losses.fused_cross_entropy_mean(logits, targets,
                                                   platform=platform)
        return jnp.mean((logits.astype(jnp.float32)
                         - targets.astype(jnp.float32)) ** 2)

    def forward(self, params, buffers, tokens, targets=None, **kw):
        """Full forward collecting every top-level activation.

        Returns ``(activations, cost, buffer_updates, new_kv)``; ``cost`` is
        None without targets, ``new_kv`` is the advanced KV state (or None).
        ``lora``/``lora_idx`` carry the stacked mixed-adapter pack + per-row
        slot indices (models/lora.py) into the module Ctx; single-adapter
        application instead binds ``lora_A/B/scale`` keys into ``params``.
        ``ragged_descs``/``ragged_rows`` (paged caches only) switch
        attention to the packed mixed-batch path: ``tokens`` is (1, Tp)
        packed, ``pos_offset`` the (1, Tp) per-token positions, and
        ``new_kv`` advances per-descriptor instead of by ``T``.
        """
        acts, cost, ctx, new_kv = self._forward(params, buffers, tokens,
                                                targets, **kw)
        return acts, cost, ctx.buffer_updates, new_kv

    def _forward(self, params, buffers, tokens, targets=None, *,
                 training=False, rng=None, kv=None, pos_offset=None,
                 skip_softmax=False, compute_dtype=None, sp_mesh=None,
                 platform=None, sp_mode="ring", ep_mesh=None, lora=None,
                 lora_idx=None, ragged_descs=None, ragged_rows=None):
        """:meth:`forward` with the module context in place of its buffer
        updates: ``(activations, cost, ctx, new_kv)``.  The cost of a model
        with several exits (a looped stack) is the expected loss over each
        token's exit distribution (``ops/losses.py::expected_exit_loss``),
        which also gives what the stack declares to report: each pass's
        mean loss and the mean exit distribution."""
        acts, logits, ctx = self._apply(
            params, buffers, tokens, training=training, rng=rng, kv=kv,
            pos_offset=pos_offset, skip_softmax=skip_softmax,
            compute_dtype=compute_dtype, sp_mesh=sp_mesh, platform=platform,
            sp_mode=sp_mode, ep_mesh=ep_mesh, lora=lora, lora_idx=lora_idx,
            ragged_descs=ragged_descs, ragged_rows=ragged_rows,
            targets=targets)
        if targets is None:
            cost = None
        elif ctx.exits is not None:
            cost, exits = losses.expected_exit_loss(
                *ctx.exits, self.looped.entropy_weight)
            for stat in self.looped.stats():
                ctx.report(stat, exits[stat.name])
        else:
            cost = self._cost_from_logits(logits, targets, platform=platform)
        if cost is not None and ctx.aux_losses:
            # Auxiliary training losses (MoE load balancing) ride the same
            # scalar so value_and_grad backpropagates them with the task loss.
            cost = cost + sum(ctx.aux_losses)
        if ctx.kv is None:
            new_kv = None
        elif ragged_descs is not None:
            new_kv = ctx.kv.with_lengths(
                ctx.kv.lengths_after_packed(ragged_descs))
        else:
            new_kv = ctx.kv.advanced(tokens.shape[-1])
        return acts, cost, ctx, new_kv

    def _train_loss_fn(self, compute_dtype, sp_mesh, platform, sp_mode,
                       ep_mesh):
        """``fn(params, buffers, x, y, rng) -> (cost, (buffer updates,
        stats))`` of one training micro-step; the stats are what the
        modules reported of it, under the names they declare."""
        def loss_fn(params, buffers, x, y, rng):
            _, cost, ctx, _ = self._forward(
                params, buffers, x, y, training=True, rng=rng,
                skip_softmax=True, compute_dtype=compute_dtype,
                sp_mesh=sp_mesh, platform=platform, sp_mode=sp_mode,
                ep_mesh=ep_mesh)
            stats, declared = ctx.reported(), sorted(self.step_stats)
            if sorted(stats) != declared:
                raise ValueError(f"the modules declare {declared} and "
                                 f"reported {sorted(stats)}")
            return cost, (ctx.buffer_updates, stats)
        return loss_fn

    def jit_forward(self, params, buffers, tokens, targets=None, *,
                    skip_softmax=False, compute_dtype=None, platform=None):
        """Jitted inference forward (cached per static configuration)."""
        key = ("fwd", targets is not None, skip_softmax, str(compute_dtype),
               platform)
        fn = self._jit_cache.get(key)
        if fn is None:
            if targets is None:
                def fwd(p, b, t):
                    return self.forward(p, b, t, None,
                                        skip_softmax=skip_softmax,
                                        compute_dtype=compute_dtype,
                                        platform=platform)
            else:
                def fwd(p, b, t, y):
                    return self.forward(p, b, t, y,
                                        skip_softmax=skip_softmax,
                                        compute_dtype=compute_dtype,
                                        platform=platform)
            fn = self._jit_cache[key] = jax.jit(fwd)
        if targets is None:
            return fn(params, buffers, tokens)
        return fn(params, buffers, tokens, targets)

    def eval_cost_fn(self, params, buffers, tokens, targets, *,
                     platform=None, sp_mesh=None, sp_mode="ring",
                     ep_mesh=None):
        """Cost-only jitted forward for ``/evaluate/``.

        Returning just the scalar lets XLA dead-code-eliminate every
        intermediate activation that :meth:`jit_forward` would materialize
        as an output; with mesh-placed params and a data-sharded batch the
        same program evaluates across every chip (the reference evaluates
        DDP-sharded across all workers, neural_net_model.py:319-354 — "no
        grad" here is simply not calling ``value_and_grad``).  ``sp_mesh``
        enables the same ring/all-to-all sequence-parallel attention the
        training epoch uses, for sequence-sharded eval batches.
        """
        key = ("evalcost", platform, sp_mesh, sp_mode, ep_mesh)
        fn = self._jit_cache.get(key)
        if fn is None:
            def fwd(p, b, t, y):
                _, cost, _, _ = self.forward(p, b, t, y, skip_softmax=True,
                                             sp_mesh=sp_mesh,
                                             sp_mode=sp_mode,
                                             platform=platform,
                                             ep_mesh=ep_mesh)
                return cost
            fn = self._jit_cache[key] = jax.jit(fwd)
        return fn(params, buffers, tokens, targets)

    # -- training -----------------------------------------------------------

    def train_epoch_fn(self, optimizer_config: dict, num_steps: int,
                       remat: bool = False, compute_dtype=None, sp_mesh=None,
                       platform=None, with_ratios: bool = True,
                       out_shardings=None, sp_mode: str = "ring",
                       pipe_cfg=None, pipe_remat: str = "block",
                       ep_mesh=None):
        """One jitted epoch: ``num_steps`` grad-accumulation micro-steps via
        ``lax.scan`` then a single optax update (reference hot loop:
        neural_net_model.py:614-677; sync deferred to the final micro-step is
        implicit here — XLA schedules gradient collectives once).

        Returns ``fn(params, opt_state, buffers, xs, ys, rng) ->
        (params, opt_state, buffers, cost, weight_update_ratios)`` where
        ``xs``/``ys`` are ``(num_steps, B, T)`` token batches.  A model
        whose modules declare statistics (``ops/modules.py::Stat``) returns
        a sixth result: the declared statistics, one dict by name, each
        folded over the epoch's micro-steps by its declared rule.

        ``with_ratios=False`` compiles a variant that skips the per-weight
        update-ratio stds (two full passes over the parameters) — the
        reference only needs them on progress-sampled epochs
        (neural_net_model.py:686-700), so the hot loop shouldn't pay them
        every step; the skipping variant returns ``ratios=None``.

        ``out_shardings=(param_shardings, opt_shardings)`` pins the updated
        params/optimizer state to the given layouts via
        ``with_sharding_constraint``.  Without the pin, GSPMD propagates
        whatever layout the update math ran in into the outputs — under
        ZeRO-1 weight-update sharding (``PENROZ_WUS=1``) that would leave
        the fresh params data-sharded instead of forcing the all-gather
        back to the parameter layout, changing their aval between epochs
        (recompile every call) and leaving cross-host-sharded params behind
        after training.
        """
        # PENROZ_REMAT=1 and pipe_remat='block' compose rather than exclude:
        # the whole-loss checkpoint discards pre/post-block residuals but
        # its backward REPLAYS the forward, and without per-block remat that
        # replay materializes every (layer, tick) block internal at once —
        # the exact residency the OOM lever exists to avoid.  Stacked, the
        # blocks run once more (fwd, outer replay, per-block replay) in
        # exchange for the bound holding everywhere.
        key = ("epoch", *self._train_key(
            optimizer_config, num_steps, remat, compute_dtype, sp_mesh,
            platform, with_ratios, out_shardings, sp_mode, ep_mesh),
            tuple(pipe_cfg[:4]) if pipe_cfg else None,
            pipe_remat if pipe_cfg is not None else None)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn

        optimizer = dsl.build_optimizer(optimizer_config)

        if pipe_cfg is None:
            loss_fn = self._train_loss_fn(compute_dtype, sp_mesh, platform,
                                          sp_mode, ep_mesh)
        else:
            piped = self._pipelined_loss_fn(pipe_cfg, compute_dtype,
                                            platform, pipe_remat=pipe_remat,
                                            sp_mode=sp_mode)

            def loss_fn(*args):
                cost, buf_upd = piped(*args)
                return cost, (buf_upd, {})

        cast, step = self._micro_step_fns(loss_fn, remat, compute_dtype)

        def epoch(params, opt_state, buffers, xs, ys, rng):
            # ONCE per epoch, outside the micro-step scan: accumulating the
            # cast's upcast gradients in fp32 yields bit-identical grads to
            # casting inside every micro-step, num_steps-1 passes fewer.
            params_c = cast(params)

            def micro(carry, batch):
                grads_acc, bufs, cost_acc, i = carry
                bufs, grads_acc, cost_acc = step(
                    params_c, bufs, grads_acc, cost_acc, *batch, rng, i)
                return (grads_acc, bufs, cost_acc, i + 1), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            init = (zeros, buffers, self.zero_cost_sum(), 0)
            (grads, new_buffers, cost_sum, _), _ = jax.lax.scan(
                micro, init, (xs, ys))
            return finalize(params, opt_state, grads, new_buffers, cost_sum)

        finalize = self._finalize_update_fn(optimizer, num_steps,
                                            out_shardings, with_ratios,
                                            pipe_cfg)
        fn = jax.jit(epoch, donate_argnums=(0, 1))
        self._jit_cache[key] = fn
        return fn

    @staticmethod
    def _train_key(optimizer_config, num_steps, remat, compute_dtype,
                   sp_mesh, platform, with_ratios, out_shardings, sp_mode,
                   ep_mesh) -> tuple:
        """What the cache keys of the fused epoch program and of the
        micro-stepped pair share."""
        shard_key = None
        if out_shardings is not None:
            shard_key = (tuple(sorted(out_shardings[0].items())),
                         tuple(jax.tree.leaves(out_shardings[1])))
        return (json.dumps(optimizer_config, sort_keys=True), int(num_steps),
                bool(remat), str(compute_dtype), sp_mesh, platform,
                bool(with_ratios), shard_key, sp_mode, ep_mesh)

    def _micro_step_fns(self, loss_fn, remat: bool, compute_dtype):
        """A training micro-step, written once for the fused epoch's scan
        and the dispatched micro program: ``(cast, step)``.  ``cast(params)``
        is the parameters in the compute dtype (its VJP upcasts the incoming
        gradients); ``step`` takes the loss and its gradient at
        ``fold_in(rng, i)``, merges the buffer updates, adds the gradient in
        float32 and folds the cost and the statistics."""
        if remat:
            loss_fn = jax.checkpoint(loss_fn)
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def cast(params):
            if compute_dtype is None:
                return params
            return {k: v.astype(compute_dtype)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v
                    for k, v in params.items()}

        def step(params_c, bufs, grads_acc, cost_acc, x, y, rng, i):
            (cost, (upd, stats)), grads = grad_fn(
                params_c, bufs, x, y, jax.random.fold_in(rng, i))
            bufs = {**bufs, **upd}
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grads_acc, grads)
            return bufs, grads_acc, self.add_costs(cost_acc, cost, stats)

        return cast, step

    def _finalize_update_fn(self, optimizer, num_steps: int, out_shardings,
                            with_ratios: bool, pipe_cfg):
        """Pure epoch tail shared by the fused epoch program and the
        micro-chunked decode-priority path: average the accumulated
        grads, apply the optax update (+sharding pins), derive the
        update-ratio stds."""

        def finalize(params, opt_state, grads, new_buffers, cost_sum):
            inv = 1.0 / num_steps
            # a mean is its sum over the micro-steps ÷ their number
            cost = cost_sum["cost"] * inv
            stats = {name: cost_sum[name] * inv if stat.reduce == "mean"
                     else cost_sum[name]
                     for name, stat in self.step_stats.items()}
            new_buffers = self.end_step(new_buffers)
            stats = (stats,) if stats else ()   # only where one is declared
            grads = jax.tree.map(
                lambda g, p: (g * inv).astype(p.dtype), grads, params)
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            if out_shardings is not None:
                new_params = jax.lax.with_sharding_constraint(
                    new_params, out_shardings[0])
                new_opt_state = jax.lax.with_sharding_constraint(
                    new_opt_state, out_shardings[1])
            if not with_ratios:
                return (new_params, new_opt_state, new_buffers, cost, None,
                        *stats)
            # per-weight update ratio std(Δw)/std(w) (reference :686-700)

            def ratio(dw_src, w_src, stacked=False):
                std = (jax.vmap(lambda a: jnp.std(a.astype(jnp.float32)))
                       if stacked else
                       lambda a: jnp.std(a.astype(jnp.float32)))
                dw, denom = std(dw_src), std(w_src)
                return jnp.where(denom > 0, dw / (denom + 1e-12), 0.0)

            if pipe_cfg is None:
                ratio_map = {k: ratio(new_params[k] - params[k], params[k])
                             for k in self.param_order}
            else:
                # Stacked leaves yield one std per layer (vmap over the
                # leading L dim) so the dashboard's per-weight curves keep
                # the canonical flat ordering.
                _, start, count, _ = pipe_cfg
                ratio_map = {}
                for k in params:
                    if k.startswith("__pipe__."):
                        r = ratio(new_params[k] - params[k], params[k],
                                  stacked=True)
                        suffix = k[len("__pipe__."):]
                        for j in range(count):
                            ratio_map[f"layers.{start + j}.{suffix}"] = r[j]
                    else:
                        ratio_map[k] = ratio(new_params[k] - params[k],
                                             params[k])
            ratios = (jnp.stack([ratio_map[k] for k in self.param_order])
                      if self.param_order else jnp.zeros((0,)))
            return (new_params, new_opt_state, new_buffers, cost, ratios,
                    *stats)

        return finalize

    def zero_cost_sum(self) -> dict:
        """What a training epoch accumulates over its micro-steps beside the
        gradient: the cost and what the modules declare to report, which
        then leaves the epoch program after its five results."""
        # an array each: the micro-stepped path donates the accumulator
        return {"cost": jnp.zeros((), jnp.float32), **{
            name: stat.empty() for name, stat in self.step_stats.items()}}

    def add_costs(self, cost_acc: dict, cost, stats: dict) -> dict:
        """:meth:`zero_cost_sum`'s accumulator after one more micro-step:
        the cost summed, each statistic folded by its declared rule."""
        return {"cost": cost_acc["cost"] + cost,
                **{name: stat.fold(cost_acc[name], stats[name])
                   for name, stat in self.step_stats.items()}}

    def train_micro_fns(self, optimizer_config: dict, num_steps: int,
                        remat: bool = False, compute_dtype=None,
                        sp_mesh=None, platform=None,
                        with_ratios: bool = True, out_shardings=None,
                        sp_mode: str = "ring", ep_mesh=None):
        """The fused :meth:`train_epoch_fn` program split at grad-accum
        micro-step boundaries for decode-priority dispatch: with the epoch
        issued one micro-step per device program, a pending ``/generate/``
        dispatch slips onto the chip between micro-steps instead of
        waiting out the whole epoch — worst-case added TTFT drops from
        one epoch to one micro-step (the reference bounds this with
        process isolation instead: main.py:461-464).

        Returns ``(micro_fn, finalize_fn)``:

        - ``micro_fn(params, buffers, grads, cost, x, y, rng, i)`` →
          ``(buffers, grads, cost)`` — one micro-step's grads accumulated
          in fp32.
        - ``finalize_fn(params, opt_state, grads, buffers, cost)`` → the
          epoch fn's results.

        Numerics match the fused epoch to fp tolerance: the same micro-step
        body (``_micro_step_fns``) and finalize body
        (``_finalize_update_fn``) — bitwise equality is NOT guaranteed (the
        standalone micro program fuses differently than the scanned epoch
        body).  The params' compute-dtype cast runs once per micro dispatch
        instead of once per epoch — identical values, ``num_steps-1`` extra
        cast passes, the price of preemptibility.  Pipelined (``pipe_cfg``)
        training keeps the fused path: one shard_map program by design.
        """
        key = ("microstep", *self._train_key(
            optimizer_config, num_steps, remat, compute_dtype, sp_mesh,
            platform, with_ratios, out_shardings, sp_mode, ep_mesh))
        cached = self._jit_cache.get(key)
        if cached is not None:
            return cached

        optimizer = dsl.build_optimizer(optimizer_config)

        cast, step = self._micro_step_fns(
            self._train_loss_fn(compute_dtype, sp_mesh, platform, sp_mode,
                                ep_mesh), remat, compute_dtype)

        def micro(params, bufs, grads_acc, cost_acc, x, y, rng, i):
            return step(cast(params), bufs, grads_acc, cost_acc, x, y, rng, i)

        finalize = self._finalize_update_fn(optimizer, num_steps,
                                            out_shardings, with_ratios,
                                            None)
        # Donation is restricted to carries a concurrent decode can never
        # see (grads/cost accumulators, the optimizer state): the whole
        # point of this path is /generate/ reading self.params and
        # self.buffers BETWEEN micro dispatches, so neither may be donated
        # (the fused epoch donates params safely because nothing yields
        # mid-program).  The price is one transient extra params copy at
        # finalize.
        fns = (jax.jit(micro, donate_argnums=(2, 3)),
               jax.jit(finalize, donate_argnums=(1, 2)))
        self._jit_cache[key] = fns
        return fns

    def _pipelined_loss_fn(self, pipe_cfg, compute_dtype, platform,
                           pipe_remat: str = "block",
                           sp_mode: str = "ring"):
        """Loss for the GPipe training layout: pre-block modules run on the
        full batch, the stacked blocks stream microbatches through the
        pipe-axis stages (``parallel/pipeline.gpipe_apply``), post-block
        modules + fused CE close the loss.  Params arrive in the mixed
        layout built by ``NeuralNetworkModel._enter_pipe_layout``:
        ``__pipe__.<suffix>`` stacked leaves plus flat non-block keys.

        Extends the reference's single DDP strategy (SURVEY §2.4 — it has
        no PP) as a depth sharding inside the same compiled program.
        """
        from penroz_tpu.parallel import pipeline
        pmesh, start, count, micro = pipe_cfg
        # MoE blocks route their balance loss + router-fraction buffers
        # through the schedule's aux channel (bubble-masked, see
        # gpipe_apply); blocks without stateful modules skip the plumbing.
        with_aux = any(isinstance(sub, M.MixtureOfExperts)
                       for sub in self.mods[start].walk())
        # SP inside the stages (both modes): the sequence axis joins the
        # schedule's manual set and attention runs the ring or Ulysses
        # body on it directly.  Layout entry validates dropout-free
        # attention and fp32 parameter storage; indivisible heads fall
        # back from alltoall to ring with a trace-time warning; MoE
        # blocks compose (the aux channel folds the seq axis).
        seq_shard = pmesh.shape[mesh_lib.SEQ_AXIS] > 1
        # The stage bodies already run inside the schedule's (partially)
        # manual region, where a kernel cannot be mapped over the mesh
        # again: they get the bare platform, not the mesh hint.
        stage_platform = attn_ops.platform_of(platform)
        block_fn = pipeline.block_fn_from_arch(
            self, start, training=True, compute_dtype=compute_dtype,
            platform=stage_platform, with_aux=with_aux, sp_manual=seq_shard,
            sp_mode=sp_mode)
        # Shape probe for the aux channel: the real block_fn references
        # the manual sequence axis, unbound outside the schedule.
        aux_probe_fn = (pipeline.block_fn_from_arch(
            self, start, training=True, compute_dtype=compute_dtype,
            platform=stage_platform, with_aux=True)
            if (with_aux and seq_shard) else None)
        pre = self.mods[:start]
        post = self.mods[start + count:]

        def loss_fn(params, buffers, x, y, rng):
            ctx = M.Ctx(params, buffers, training=True, rng=rng,
                        compute_dtype=compute_dtype, platform=platform)
            h = x
            for mod in pre:
                h = mod.apply(h, ctx)
            stacked = {k[len("__pipe__."):]: v for k, v in params.items()
                       if k.startswith("__pipe__.")}
            res = pipeline.gpipe_apply(block_fn, stacked, h, pmesh, micro,
                                       rng=jax.random.fold_in(rng, 0x9e3779),
                                       remat=pipe_remat, with_aux=with_aux,
                                       seq_shard=seq_shard,
                                       aux_probe_fn=aux_probe_fn)
            if with_aux:
                h, aux_sums = res
                # Per-(layer, microbatch) sums -> mean over microbatches.
                # Microbatches partition the rows, so the fraction means
                # equal the sequential whole-batch fractions exactly; the
                # balance loss matches the grad-accum path where each
                # micro-step's aux joins its own cost and costs average.
                ctx.aux_losses.append(jnp.sum(aux_sums["loss"]) / micro)
                for key, leaf in aux_sums.items():
                    if key == "loss":
                        continue
                    suffix = key[len("buf."):]
                    for j in range(count):
                        ctx.buffer_updates[
                            f"layers.{start + j}.{suffix}"] = leaf[j] / micro
            else:
                h = res
            logits = None
            for mod in post:
                if isinstance(mod, M.Softmax):
                    if logits is None:
                        logits = h  # skip_softmax semantics (cost on logits)
                    continue
                h = mod.apply(h, ctx)
            if logits is None:
                logits = h
            cost = self._cost_from_logits(logits, y, platform=platform)
            if ctx.aux_losses:
                cost = cost + sum(ctx.aux_losses)
            return cost, ctx.buffer_updates

        return loss_fn

    # -- decode -------------------------------------------------------------

    def _decode_step(self, params, buffers, kv, tokens, rng, temp, *,
                     greedy, top_k, compute_dtype, platform=None,
                     lora=None, lora_idx=None):
        """Feed tokens through the stack with the KV cache, sample the next
        token on-device (reference samples on host: :393-405)."""
        acts, _, _, new_kv = self.forward(
            params, buffers, tokens, None, kv=kv, pos_offset=kv.length,
            skip_softmax=True, compute_dtype=compute_dtype,
            platform=platform, lora=lora, lora_idx=lora_idx)
        logits = acts[-1]
        if logits.ndim == 3:
            logits = logits[:, -1, :]
        tok = self._sample(logits, rng, temp, greedy=greedy, top_k=top_k)
        return tok[:, None], new_kv

    @staticmethod
    def _sample(logits, rng, temp, *, greedy, top_k):
        """(B,) next tokens from (B, V) logits: argmax | top-k | categorical
        (reference sampling: neural_net_model.py:393-405, on-device)."""
        logits = logits.astype(jnp.float32)
        if greedy:
            tok = jnp.argmax(logits, axis=-1)
        else:
            logits = logits / jnp.maximum(temp, 1e-6)
            if top_k is not None:
                vals, idx = jax.lax.top_k(logits, int(top_k))
                choice = jax.random.categorical(rng, vals)
                tok = jnp.take_along_axis(idx, choice[..., None], -1)[..., 0]
            else:
                tok = jax.random.categorical(rng, logits)
        return tok.astype(jnp.int32)

    @staticmethod
    def _sample_packed(logits, rng, row_ids, positions, temp, top_k):
        """(Tp,) sampled tokens from packed (Tp, V) logits with a
        POSITIONAL key per slot: ``fold_in(fold_in(rng, row), position)``.
        A (row, position) pair draws the same token no matter which packed
        slot, superstep, chunk split or pipeline micro-block it rides in —
        the invariance that lets seeded temperature>0 streams stay
        identical across spec-on/off (rejection sampling over point-mass
        drafts reduces to prefix matching against these draws) and across
        pipeline stage counts.  Padding slots carry ``row_ids < 0``;
        clipped to 0, sampled, and discarded by the host replay."""
        logits = logits.astype(jnp.float32)
        logits = logits / jnp.maximum(temp, 1e-6)
        keys = jax.vmap(
            lambda rid, pos: jax.random.fold_in(
                jax.random.fold_in(rng, jnp.clip(rid, 0)),
                jnp.maximum(pos, 0))
        )(row_ids.astype(jnp.int32), positions.astype(jnp.int32))
        if top_k is not None:
            vals, idx = jax.lax.top_k(logits, int(top_k))
            choice = jax.vmap(jax.random.categorical)(keys, vals)
            tok = jnp.take_along_axis(idx, choice[..., None], -1)[..., 0]
        else:
            tok = jax.vmap(jax.random.categorical)(keys, logits)
        return tok.astype(jnp.int32)

    def decode_fn(self):
        """Dispatcher for single decode/prefill steps (jits per static
        (greedy, top_k, dtype); shapes retrace automatically)."""

        def decode(params, buffers, kv, tokens, rng, temp, *,
                   compute_dtype=None, greedy=False, top_k=None,
                   platform=None):
            key = ("decode", bool(greedy), top_k, str(compute_dtype),
                   platform)
            fn = self._jit_cache.get(key)
            if fn is None:
                def step(p, b, k, t, r, tmp):
                    return self._decode_step(p, b, k, t, r, tmp,
                                             greedy=greedy, top_k=top_k,
                                             compute_dtype=compute_dtype,
                                             platform=platform)
                fn = self._jit_cache[key] = jax.jit(step, donate_argnums=(2,))
            return fn(params, buffers, kv, tokens, rng, temp)

        return decode

    def decode_chunk(self, params, buffers, kv, last_tok, rng, temp, *,
                     chunk: int, greedy=False, top_k=None, compute_dtype=None,
                     platform=None):
        """Run ``chunk`` fused decode+sample steps in one dispatch."""
        key = ("chunk", int(chunk), bool(greedy), top_k, str(compute_dtype),
               platform)
        fn = self._jit_cache.get(key)
        if fn is None:
            def run(p, b, kv0, tok0, r, tmp):
                def step(carry, i):
                    kv_c, tok = carry
                    new_tok, kv_c = self._decode_step(
                        p, b, kv_c, tok, jax.random.fold_in(r, i), tmp,
                        greedy=greedy, top_k=top_k,
                        compute_dtype=compute_dtype, platform=platform)
                    return (kv_c, new_tok), new_tok[:, 0]

                (kv_c, _), toks = jax.lax.scan(step, (kv0, tok0),
                                               jnp.arange(chunk))
                return toks.T, kv_c

            fn = self._jit_cache[key] = jax.jit(run, donate_argnums=(2,))
        return fn(params, buffers, kv, last_tok, rng, temp)

    # -- diagnostics --------------------------------------------------------

    def stats_grads(self, params, buffers, x, y, compute_dtype=None,
                    platform=None):
        """Activations, activation-gradients and weight-gradients for one
        batch — the /stats/ inputs.  Activation grads come from an explicit
        zero-delta VJP (JAX has no ``retain_grad``; reference :643-646)."""
        acts, _, _, _ = self.jit_forward(params, buffers, x, y,
                                         skip_softmax=True,
                                         compute_dtype=compute_dtype,
                                         platform=platform)
        deltas = [jnp.zeros(a.shape, a.dtype) for a in acts]

        key = ("statsgrad", str(compute_dtype), platform)
        fn = self._jit_cache.get(key)
        if fn is None:
            def f(p, d, xb, yb, bufs):
                ctx = M.Ctx(p, bufs, training=False,
                            compute_dtype=compute_dtype, platform=platform)
                h = xb
                i = 0
                for mod in self.mods:
                    if isinstance(mod, M.Softmax):
                        continue
                    h = mod.apply(h, ctx) + d[i]
                    i += 1
                return self._cost_from_logits(h, yb, platform=platform)

            fn = self._jit_cache[key] = jax.jit(
                lambda p, d, xb, yb, bufs:
                jax.grad(f, argnums=(0, 1))(p, d, xb, yb, bufs))
        weight_grads, act_grads = fn(params, deltas, x, y, buffers)
        return acts, act_grads, weight_grads


class ServePipeline:
    """Stage partition of a compiled arch for MPMD pipeline serving
    (``PENROZ_SERVE_PIPE_STAGES``).

    Unlike the training pipeline (``__pipe__`` stacked layouts + ppermute
    inside one jit, parallel/pipeline.py) the serving pipeline is MPMD:
    each stage is its own :class:`CompiledArch` over a contiguous slice of
    the layer DSL, compiling and dispatching its own per-stage program
    while the scheduler hands activations across stage boundaries
    (PAPERS.md #3).  The slice boundaries come from
    ``parallel.pipeline.serve_stage_bounds`` — contiguous runs of the
    repeated transformer block, with the prologue (embeddings) glued to
    the first stage and the epilogue (final norm / head) to the last.

    Per-stage KV: stage ``s`` owns attention layers ``kv_bounds[s] =
    [lo, hi)`` of the full paged cache — its pools live on its own stage
    mesh (``ops.kv_cache.stage_kv_view`` / ``merge_stage_kv``), which is
    what drops per-device HBM ~1/S.  Stage archs index their attention
    layers 0.. locally, matching the sliced pool lists exactly.

    Params/buffers are NOT copied: :meth:`stage_params` re-keys the
    canonical flat dict (``layers.{i}.*`` → ``layers.{i-lo}.*``) per
    dispatch — dict slicing over array references, no device traffic.
    """

    def __init__(self, arch: "CompiledArch", stages: int):
        arch.refuse_looped("serving pipeline stages "
                           "(PENROZ_SERVE_PIPE_STAGES)")
        from penroz_tpu.parallel import pipeline
        if arch.ssm_specs:
            raise ValueError(
                "pipeline serving does not support SSM/recurrent blocks: "
                "stage_kv_view slices attention pools only and would drop "
                "the per-row recurrent state")
        self.stages = int(stages)
        self.bounds = pipeline.serve_stage_bounds(arch.layers_dsl,
                                                  self.stages)
        self.archs = [CompiledArch.get(arch.layers_dsl[lo:hi])
                      for lo, hi in self.bounds]
        self.kv_bounds: list[tuple] = []
        off = 0
        for s, stage_arch in enumerate(self.archs):
            n = len(stage_arch.kv_specs)
            if n == 0:
                raise ValueError(
                    f"pipeline stage {s} owns no attention layers; lower "
                    f"PENROZ_SERVE_PIPE_STAGES (bounds {self.bounds[s]})")
            self.kv_bounds.append((off, off + n))
            off += n
        if off != len(arch.kv_specs):
            raise ValueError(
                f"stage KV partition covers {off} attention layers, "
                f"model has {len(arch.kv_specs)}")
        # Per-stage TP meshes, filled by _enter_serve_pipe_mesh when the
        # group really spans devices (None = degenerate single-device
        # layout — no placement, no per-dispatch re-staging needed).
        self.meshes = None

    def stage_key_range(self, s: int):
        """Half-open top-level DSL entry range owned by stage ``s``."""
        return self.bounds[s]

    def _rekey(self, flat: dict, s: int) -> dict:
        lo, hi = self.bounds[s]
        out = {}
        for k, v in flat.items():
            if not k.startswith("layers."):
                if s == 0:  # prologue state rides with the first stage
                    out[k] = v
                continue
            idx, _, suffix = k[len("layers."):].partition(".")
            i = int(idx)
            if lo <= i < hi:
                out[f"layers.{i - lo}.{suffix}"] = v
        return out

    def stage_params(self, params: dict, s: int) -> dict:
        return self._rekey(params, s)

    def stage_buffers(self, buffers: dict, s: int) -> dict:
        return self._rekey(buffers, s)


class NeuralNetworkModel:
    """Full model lifecycle facade (reference: NeuralNetworkModel,
    neural_net_model.py:28-779)."""

    def __init__(self, model_id: str, mapper: Mapper):
        self.model_id = model_id
        self.layers_dsl = mapper.layers
        self.optimizer_config = mapper.optimizer
        self.arch = CompiledArch.get(mapper.layers)
        self.params, self.buffers = mapper.init_params(self.arch.mods)
        self.opt_state = mapper.to_optimizer().init(self.params)
        self.progress: list[dict] = []
        self.avg_cost: Optional[float] = None
        self.avg_cost_history: list[float] = []
        self.stats: Optional[dict] = None
        self.status = {"code": "Created", "message": "Model created"}
        self.device = None
        self._sample_rng = jax.random.key(0)
        # (start, count) while params live in the GPipe stacked layout
        self._pipe_layout: Optional[tuple] = None

    # -- introspection ------------------------------------------------------

    @property
    def num_params(self) -> int:
        return sum(int(np.prod(v.shape)) for v in self.params.values())

    @property
    def dtype(self):
        for v in self.params.values():
            if jnp.issubdtype(v.dtype, jnp.floating):
                return v.dtype
        return jnp.dtype(jnp.float32)

    def state_dict(self) -> dict:
        """Flat params + buffers under reference-compatible key names."""
        out = {k: np.asarray(v) for k, v in self.params.items()}
        out.update({k: np.asarray(v) for k, v in self.buffers.items()})
        return out

    def to(self, dtype=None):
        """Cast floating params/buffers (reference bf16 policy:
        neural_net_model.py:145-157)."""
        if dtype is not None:
            self.params = {
                k: v.astype(dtype) if jnp.issubdtype(v.dtype, jnp.floating)
                else v for k, v in self.params.items()}
            self.buffers = {
                k: v.astype(dtype) if jnp.issubdtype(v.dtype, jnp.floating)
                else v for k, v in self.buffers.items()}
        return self

    def to_device(self, device: Optional[str]):
        dev = _resolve_device(device)
        if dev is not None:
            self.params = jax.device_put(self.params, dev)
            self.buffers = jax.device_put(self.buffers, dev)
            self.opt_state = jax.device_put(self.opt_state, dev)
            self.device = dev
        return self

    @property
    def _platform(self) -> Optional[str]:
        """Platform the model's programs run on.  A model explicitly placed
        (device='cpu' on a TPU-attached host) must not trace TPU kernels
        that cannot lower for its backend; params placement is what
        actually decides where jit runs."""
        if self.device is not None:
            return self.device.platform
        try:
            p = next(iter(self.params.values()))
            if isinstance(p, jax.Array) and not isinstance(p, jax.core.Tracer):
                return next(iter(p.devices())).platform
        except StopIteration:
            pass
        return None

    @property
    def _placement(self):
        """``platform`` hint for the Pallas kernel gates (ops/attention.py):
        the platform string, or — when the params live on a mesh of more
        than one device, i.e. GSPMD will partition every program they
        enter — a ``Placement`` that also names that mesh, so kernel calls
        get mapped over its shards."""
        platform = self._platform
        sharding = getattr(next(iter(self.params.values()), None),
                           "sharding", None)
        if (isinstance(sharding, jax.sharding.NamedSharding)
                and sharding.mesh.size > 1):
            return attn_ops.Placement(platform, sharding.mesh)
        return platform

    # -- inference ----------------------------------------------------------

    def _as_input(self, data):
        try:
            arr = np.asarray(data)
        except ValueError:
            raise ValueError(
                "input rows have inconsistent lengths; expected a "
                "rectangular batch like [[1, 2, 3], [4, 5, 6]]")
        if arr.dtype.kind in "iu":
            if self.arch.attn_layers and arr.ndim != 2:
                # A flat token list on a sequence model dies deep in the
                # stack with an opaque unpack error; say what's wrong at
                # the API boundary instead (→ HTTP 400).
                raise ValueError(
                    f"token input must be 2-D (batch, length) for this "
                    f"model, e.g. [[1, 2, 3]]; got {arr.ndim}-D")
            return jnp.asarray(arr.astype(np.int64), jnp.int32)
        return jnp.asarray(arr).astype(self.dtype)

    def compute_output(self, input, target=None):
        """Raw forward; returns (final activation as lists, cost or None)
        (reference: neural_net_model.py:273-298)."""
        x = self._as_input(input)
        if target is None:
            acts, cost, _, _ = self.arch.jit_forward(self.params, self.buffers,
                                                     x,
                                                     platform=self._placement)
        else:
            t = np.asarray(target)
            if self.arch.classification:
                t = jnp.asarray(t.astype(np.int64), jnp.int32)
            else:
                t = jnp.asarray(t, jnp.float32)
            acts, cost, _, _ = self.arch.jit_forward(self.params, self.buffers,
                                                     x, t,
                                                     platform=self._placement)
        output = np.asarray(acts[-1], np.float32).tolist()
        return output, (float(cost) if cost is not None else None)

    def evaluate_model(self, dataset_id, target_dataset_id, shard, epochs,
                       batch_size, block_size, step_size) -> float:
        """Forward-only evaluation with the training loader math
        (reference: neural_net_model.py:300-358).

        Reference parity: one ``(batch_size, block_size)`` buffer is loaded
        per epoch and forwarded ``num_steps`` times under no-grad
        (:337-351) — identical data each step, so we forward once and weight
        by ``1/epochs`` (numerically equal, ``num_steps``× fewer FLOPs).
        The result is averaged across processes like the reference's
        ``ddp_all_reduce`` (:352-354).  Multi-host contract: as with
        ``/train/`` over a global mesh, every host's server must receive
        the same request — the final reduction is a collective and a
        single-host request would block until the distributed runtime
        times out.
        """
        from penroz_tpu.data.loaders import Loader
        world = dist.process_count()
        rank = dist.process_index()
        buffer_size = batch_size * block_size
        loader = Loader(dataset_id, begin_shard=shard,
                        begin_idx=buffer_size * rank, buffer_size=buffer_size,
                        idx_offset=buffer_size * world)
        target_loader = None
        if target_dataset_id:
            target_loader = Loader(target_dataset_id, begin_shard=shard,
                                   begin_idx=buffer_size * rank,
                                   buffer_size=buffer_size,
                                   idx_offset=buffer_size * world)
        mesh = self._eval_mesh(batch_size, block_size)
        sp_mesh = None
        ep_mesh = None
        sp_mode = os.environ.get("PENROZ_SP_MODE", "ring")
        if mesh is not None:
            log.info("Evaluating over device mesh %s", dict(mesh.shape))
            if mesh.shape[mesh_lib.SEQ_AXIS] > 1:
                # Sequence-parallel eval: shard the block over the seq
                # axis and run the ring/all-to-all attention, same as the
                # training epoch — without this the seq-axis chips would
                # do purely redundant replicated work.
                if sp_mode not in ("ring", "alltoall"):
                    raise ValueError(f"PENROZ_SP_MODE={sp_mode!r}; "
                                     "expected 'ring' or 'alltoall'")
                sp_mesh = mesh
            if mesh.shape[mesh_lib.EXPERT_AXIS] > 1:
                ep_mesh = mesh
            # Mirror the training layout (TP over `model`, experts over
            # `expert`, ZeRO-3 over `data` when PENROZ_FSDP=1) so an
            # already-mesh-placed model is a no-op and a freshly loaded one
            # gets the layout its size may require (a TP-trained model
            # larger than one chip cannot evaluate single-device at all).
            fsdp = os.environ.get("PENROZ_FSDP", "0") == "1"
            self.params = sharding_lib.shard_params(self.params, mesh,
                                                    fsdp=fsdp)
            self.buffers = {
                k: sharding_lib.place(v, mesh_lib.replicated(mesh))
                for k, v in self.buffers.items()}
        avg_cost = 0.0
        for _ in range(epochs):
            if target_loader is not None:
                x, _ = loader.next_batch(target_offset=0)
                y, _ = target_loader.next_batch(target_offset=0)
            else:
                x, y = loader.next_batch()
            x = x.reshape(batch_size, block_size)
            y = y.reshape(batch_size, block_size)
            if mesh is not None:
                x = sharding_lib.global_batch(
                    x, mesh, shard_sequence=sp_mesh is not None)
                y = sharding_lib.global_batch(
                    y, mesh, shard_sequence=sp_mesh is not None)
            else:
                x = jnp.asarray(x)
                y = jnp.asarray(y)
            cost = self.arch.eval_cost_fn(self.params, self.buffers, x, y,
                                          platform=self._placement,
                                          sp_mesh=sp_mesh, sp_mode=sp_mode,
                                          ep_mesh=ep_mesh)
            avg_cost += float(cost) / epochs
        # Under a global multi-host mesh the compiled cost is already the
        # global-batch mean (identical on every process), so this reduce is
        # an identity; it remains load-bearing for the mesh-less multi-host
        # path, where each process averaged only its own stride.
        return dist.all_reduce_mean(avg_cost)

    # -- training -----------------------------------------------------------

    def train_model(self, dataset_id, shard=0, epochs=1, batch_size=1,
                    block_size=1024, step_size=1, setup_span=None):
        """Grad-accumulated training with progress/stats bookkeeping and
        periodic checkpoints (reference: neural_net_model.py:552-722).

        Reference micro-batch semantics (:581-586, :629-631): every
        micro-step consumes a full ``(batch_size, block_size)`` buffer from
        the loader; ``step_size`` only sets how many such micro-steps
        accumulate into one optimizer step
        (:func:`accumulation_steps`: ``batch_size // (step_size * world)``;
        a fraction asks for more micro-steps than the batch has rows).
        Progress/stats reset at train start (:597-601); ``speedPerSec``
        counts ``buffer_size`` tokens per epoch exactly as the reference
        does (:684-703), although an epoch consumes ``num_steps`` buffers.

        Per-epoch cost under a multi-host mesh is computed over the global
        batch inside the compiled program, which subsumes the reference's
        per-epoch ``ddp_all_reduce(cost)`` (:664-665).

        ``setup_span``: the caller's open ``penroz/train_setup`` span
        (:meth:`train_model_on_device` opens it before deserializing); it
        is closed here, before the first epoch.  Without one, set-up is
        this method's own part of it.
        """
        from penroz_tpu.data.loaders import Loader
        if setup_span is None:
            setup_span = tracing.span("penroz/train_setup").__enter__()
        master = dist.master_proc()
        saves_shards = False
        epoch = 0
        # Bumped at request START so it advances in lockstep on every host
        # regardless of how this run ends (multi-host contract: every host
        # receives the same requests) — the train-end barrier id derives
        # from it and must never desynchronize.  Module-level (not an
        # instance attribute): /train/ deserializes a fresh model object
        # per request, but the counter must survive across them for the
        # process lifetime.  A single host restarting would reset only its
        # own counters, but that state is unreachable: jax.distributed
        # requires every process alive, so one host restarting forces a
        # fleet-wide restart that resets all counters together.
        _TRAIN_SEQ[self.model_id] = train_seq = \
            _TRAIN_SEQ.get(self.model_id, 0) + 1
        try:
            world = dist.process_count()
            rank = dist.process_index()
            buffer_size = batch_size * block_size
            # Reset run state before anything that can raise (mesh config,
            # missing dataset): an Error from THIS request must not present
            # the previous run's progress as its own.
            self.progress = []
            self.stats = None
            mesh = self._training_mesh(batch_size, block_size)
            # When pipeline stages span processes, what's distributed
            # across hosts is the MODEL, not the data: every process feeds
            # the same batch (rank striding off, DP width 1 in the
            # reference buffer math) and the within-stage data axis shards
            # those rows locally.
            pipe_over_hosts = (world > 1 and mesh is not None
                               and mesh.shape[mesh_lib.PIPE_AXIS] > 1)
            dp_world = 1 if pipe_over_hosts else world
            dp_rank = 0 if pipe_over_hosts else rank
            num_steps = accumulation_steps(batch_size, step_size, dp_world)
            loader = Loader(dataset_id, begin_shard=shard,
                            begin_idx=buffer_size * dp_rank,
                            buffer_size=buffer_size,
                            idx_offset=buffer_size * dp_world)
            self.status = {"code": "Training",
                           "message": f"Training on {dataset_id}"}
            if master:
                self.serialize()
            sp_mesh = None
            ep_mesh = None
            epoch_out_shardings = None
            pipe_cfg = None
            if mesh is not None and mesh.shape[mesh_lib.PIPE_AXIS] > 1:
                log.info("Training over device mesh %s", dict(mesh.shape))
                pipe_cfg, epoch_out_shardings = self._enter_pipe_layout(
                    mesh, batch_size)
                self.buffers = {
                    k: sharding_lib.place(v, mesh_lib.replicated(mesh))
                    for k, v in self.buffers.items()}
            elif mesh is not None:
                log.info("Training over device mesh %s", dict(mesh.shape))
                # ZeRO ladder on top of the TP layout (arXiv:2004.13336):
                # PENROZ_WUS=1 spreads the optimizer moments over the data
                # axis (each DP replica updates 1/data of the weights);
                # PENROZ_FSDP=1 also shards the params themselves (ZeRO-3 —
                # XLA all-gathers each weight just-in-time per matmul).
                # The epoch fn's out_shardings pin keeps both layouts
                # stable across steps instead of whatever GSPMD propagates.
                fsdp = os.environ.get("PENROZ_FSDP", "0") == "1"
                wus = fsdp or os.environ.get("PENROZ_WUS", "0") == "1"
                self.params = sharding_lib.shard_params(self.params, mesh,
                                                        fsdp=fsdp)
                epoch_out_shardings = (
                    sharding_lib.param_shardings(self.params, mesh,
                                                 fsdp=fsdp),
                    sharding_lib.opt_state_sharding_tree(self.opt_state,
                                                         self.params, mesh,
                                                         wus=wus))
                self.opt_state = sharding_lib.place_tree(
                    self.opt_state, epoch_out_shardings[1])
                self.buffers = {
                    k: sharding_lib.place(v, mesh_lib.replicated(mesh))
                    for k, v in self.buffers.items()}
                if mesh.shape[mesh_lib.SEQ_AXIS] > 1:
                    sp_mesh = mesh
                if mesh.shape[mesh_lib.EXPERT_AXIS] > 1:
                    # MoE capacity dispatch routes tokens over the expert
                    # axis via all_to_all (ops/modules._apply_capacity_ep)
                    # instead of the dense-combine psum.
                    ep_mesh = mesh
            # With cross-host-sharded state every process must persist its
            # own shard file at each checkpoint; the master also writes the
            # metadata blob (serialize() handles the split internally).
            # Checked over ALL persisted items: under PENROZ_WUS only the
            # optimizer moments are cross-host data-sharded (params stay
            # host-readable), and under PENROZ_FSDP the params are too —
            # both need the shard-file treatment, so a params-only check
            # would tear either checkpoint.
            saves_shards = (mesh is not None and world > 1
                            and not all(self._is_host_readable(v)
                                        for v in
                                        self._checkpoint_items().values()))
            # PENROZ_REMAT=1 wraps the whole loss of a micro-step in one
            # jax.checkpoint: the backward replays the whole forward first, so
            # it costs ~1/3 more FLOPs and the replay holds every activation
            # again; recomputation that does bound memory is per application,
            # as ops/modules.py::Looped does for its blocks and exits.
            remat = os.environ.get("PENROZ_REMAT", "0") == "1"
            # PENROZ_PIPE_REMAT selects the pipelined path's activation
            # schedule: 'block' (default — backward recomputes each block
            # tick-by-tick, bounding stage memory to live microbatch
            # activations the way 1F1B does) or 'none' (save everything).
            pipe_remat = os.environ.get("PENROZ_PIPE_REMAT", "block")
            if pipe_remat not in ("none", "block"):
                raise ValueError(f"PENROZ_PIPE_REMAT={pipe_remat!r}; "
                                 "expected 'none' or 'block'")
            # Reference parity: training autocasts to bf16 on CUDA
            # (neural_net_model.py:567-578) and stays full-precision on CPU.
            # The TPU-native equivalent is bf16 compute on TPU — params and
            # optimizer state remain fp32; no GradScaler is needed on TPU.
            # PENROZ_TRAIN_DTYPE=float32|bfloat16 overrides.
            dtype_env = os.environ.get("PENROZ_TRAIN_DTYPE", "")
            if dtype_env:
                compute_dtype = (None if dtype_env == "float32"
                                 else jnp.dtype(dtype_env))
            elif (self._platform or jax.default_backend()) == "tpu":
                compute_dtype = jnp.bfloat16
            else:
                compute_dtype = None
            # PENROZ_SP_MODE selects the sequence-parallel attention:
            # 'ring' (ppermute rotation, default) or 'alltoall' (Ulysses
            # head re-partitioning; needs heads divisible by the axis).
            sp_mode = os.environ.get("PENROZ_SP_MODE", "ring")
            if sp_mode not in ("ring", "alltoall"):
                raise ValueError(f"PENROZ_SP_MODE={sp_mode!r}; expected "
                                 "'ring' or 'alltoall'")
            if sp_mode == "alltoall" and sp_mesh is not None:
                from penroz_tpu.parallel import alltoall_attention as a2a
                undiv = [i for i, mod in enumerate(self.arch.attn_layers)
                         if not a2a.alltoall_supported(
                             mod.num_heads, mod.num_kv_heads, sp_mesh)]
                if undiv:
                    log.warning(
                        "PENROZ_SP_MODE=alltoall: attention layer(s) %s "
                        "have head counts not divisible by the sequence "
                        "axis (%d) and fall back to ring attention",
                        undiv, sp_mesh.shape[mesh_lib.SEQ_AXIS])
            epoch_fn = self.arch.train_epoch_fn(
                self.optimizer_config, num_steps, remat=remat,
                compute_dtype=compute_dtype, sp_mesh=sp_mesh,
                platform=self._placement,
                out_shardings=epoch_out_shardings, sp_mode=sp_mode,
                pipe_cfg=pipe_cfg, pipe_remat=pipe_remat, ep_mesh=ep_mesh)
            # Non-sampled epochs skip the two full parameter passes the
            # update-ratio stds cost.  The choice is a pure function of the
            # epoch index so every host runs the same compiled program
            # (collective schedules must match under a multi-host mesh).
            sample_every = max(1, epochs // 100)
            epoch_fn_fast = (
                self.arch.train_epoch_fn(self.optimizer_config, num_steps,
                                         remat=remat,
                                         compute_dtype=compute_dtype,
                                         sp_mesh=sp_mesh,
                                         platform=self._placement,
                                         with_ratios=False,
                                         out_shardings=epoch_out_shardings,
                                         sp_mode=sp_mode,
                                         pipe_cfg=pipe_cfg,
                                         pipe_remat=pipe_remat,
                                         ep_mesh=ep_mesh)
                if sample_every > 1 else epoch_fn)
            rng = jax.random.key(0)
            last_save = time.monotonic()
            last_stats = time.monotonic()
            # Stats refresh runs a full instrumented pass (the reference
            # histograms grads already retained by its backward,
            # :643-646, which is nearly free; ours re-derives them), so
            # it gets its own, longer cadence than the 10s checkpoint.
            stats_interval = float(
                os.environ.get("PENROZ_STATS_INTERVAL", "60"))
            last_batch = None  # host-local numpy micro-batch for /stats/
            setup_span.close()
            for epoch in range(epochs):
                # Decode-priority window: queued /generate/ dispatches get
                # the chip before the next epoch program is enqueued.
                _yield_to_decodes()
                t0 = time.monotonic()
                long_training = t0 - last_save >= 10
                if saves_shards:
                    # All hosts must agree on checkpoint epochs or the blob
                    # and the per-host shard files would mix training steps;
                    # a tiny scalar reduction makes the clock-based decision
                    # deterministic across the fleet.
                    long_training = dist.all_reduce_mean(
                        1.0 if long_training else 0.0) >= 0.5
                with tracing.span("penroz/load_batch",
                                  tokens=num_steps * buffer_size
                                  ) as batch_span:
                    scanned = loader.scan_seconds
                    gathered = loader.gather_seconds
                    xs, ys = [], []
                    for _ in range(num_steps):
                        x, y = loader.next_batch()
                        xs.append(x.reshape(batch_size, block_size))
                        ys.append(y.reshape(batch_size, block_size))
                    # stay on host: global_batch/jit place them exactly once
                    xs = np.stack(xs)
                    ys = np.stack(ys)
                    # the loader's own account of the span: what it spent
                    # learning what there is to read / bringing the tokens
                    batch_span.set(
                        scan_ms=round(
                            1e3 * (loader.scan_seconds - scanned), 3),
                        gather_ms=round(
                            1e3 * (loader.gather_seconds - gathered), 3))
                last_batch = (xs[-1], ys[-1])
                if mesh is not None:
                    xs = sharding_lib.global_batch(
                        xs, mesh, leading_steps=True,
                        shard_sequence=sp_mesh is not None,
                        process_replicated=pipe_over_hosts)
                    ys = sharding_lib.global_batch(
                        ys, mesh, leading_steps=True,
                        shard_sequence=sp_mesh is not None,
                        process_replicated=pipe_over_hosts)
                sampled = epoch % sample_every == 0
                fn = epoch_fn if sampled else epoch_fn_fast
                # Micro-step granularity when a decode is in flight: the
                # fused epoch is one device program a /generate/ can only
                # wait out; chunked dispatch bounds the decode's wait to
                # one micro-step (+ its own work).  Fused otherwise — the
                # chunked path pays per-dispatch overhead num_steps times.
                use_micro = (pipe_cfg is None and world == 1
                             and num_steps > 1 and decode_pending() > 0
                             and float(os.environ.get(
                                 "PENROZ_DECODE_PRIORITY_MS", "1000")) > 0)
                # The epoch ends when its cost is on the host: the call
                # returns at dispatch, float(cost) waits for the device.
                with tracing.span("penroz/train_epoch", epoch=epoch + 1,
                                  tokens=num_steps * buffer_size,
                                  sampled=sampled,
                                  microstepped=use_micro) as epoch_span:
                    with tracing.span("penroz/train_dispatch"):
                        if use_micro:
                            out = self._train_epoch_microstepped(
                                xs, ys, jax.random.fold_in(rng, epoch),
                                num_steps, remat=remat,
                                compute_dtype=compute_dtype,
                                sp_mesh=sp_mesh,
                                out_shardings=epoch_out_shardings,
                                sp_mode=sp_mode, ep_mesh=ep_mesh,
                                with_ratios=sampled)
                        else:
                            out = fn(
                                self.params, self.opt_state, self.buffers,
                                xs, ys, jax.random.fold_in(rng, epoch))
                        (self.params, self.opt_state, self.buffers,
                         cost, ratios) = out[:5]
                    with tracing.span("penroz/train_wait"):
                        cost = float(cost)
                        # what the modules declare to report, one host read
                        declared = self.arch.step_stats
                        stats = ({k: declared[k].on_host(v)
                                  for k, v in out[5].items()}
                                 if len(out) > 5 else {})
                    epoch_span.set(**tracing.train_stat_counters(
                        stats, {k: declared[k].family for k in stats}))
                duration = time.monotonic() - t0
                if master:
                    if epoch % sample_every == 0:
                        self.progress.append({
                            "epoch": epoch + 1,
                            "cost": cost,
                            "durationInSecs": duration,
                            "speedPerSec": buffer_size / max(duration, 1e-9),
                            "weight_upd_ratio":
                                np.asarray(ratios, np.float64).tolist(),
                            **stats,
                        })
                    log.info("Epoch %d: cost=%.4f %.0f tokens/sec",
                             epoch + 1, cost,
                             buffer_size / max(duration, 1e-9))
                if long_training:
                    if master:
                        refresh = (time.monotonic() - last_stats
                                   >= stats_interval)
                        with tracing.span("penroz/train_stats",
                                          refreshed=refresh):
                            self._record_overall_progress(
                                last_batch if refresh else None)
                        if refresh:
                            last_stats = time.monotonic()
                    if master or saves_shards:
                        self.serialize(tag=epoch)
                    last_save = time.monotonic()
            self._exit_pipe_layout()
            self.status = {"code": "Trained",
                           "message": f"Trained {epochs} epoch(s)"}
            if master:
                with tracing.span("penroz/train_stats", refreshed=True):
                    self._record_overall_progress(last_batch)
            if master or saves_shards:
                self.serialize(tag=epochs)
            # Fence the run's end across processes: the master's post-train
            # bookkeeping (stats capture compiles a fresh program) can take
            # minutes, and a peer racing ahead into the next collective
            # (e.g. /evaluate/) would hit the ~30s lazy comm-group init
            # timeout waiting for this host.  RPC barrier, so it tolerates
            # the wait without any device group existing yet.  The id
            # comes from the train-start counter (in lockstep on every
            # host even if a peer errored mid-run); a failure here is a
            # pacing miss, not a training failure — the run is already
            # Trained and checkpointed, so never regress it to Error.
            try:
                dist.barrier(f"train_end_{self.model_id}_{train_seq}")
            except Exception:  # noqa: BLE001
                log.warning("train-end barrier failed; a peer may have "
                            "errored mid-run", exc_info=True)
        except Exception as e:  # noqa: BLE001
            setup_span.close()
            try:
                # Hosts reach this handler independently — never run the
                # (collective) cross-host unstack one-sided.
                self._exit_pipe_layout(local_only=dist.is_distributed())
            except Exception:  # noqa: BLE001
                log.exception("Failed to restore flat param layout")
            self.status = {"code": "Error", "message": str(e)}
            # Untagged on purpose: hosts reach this handler independently
            # (possibly at different epochs, possibly only one of them), so
            # a shard-file rewrite here could tear the last consistent
            # checkpoint.  serialize() degrades an untagged sharded save to
            # a master-only metadata update — Error status is recorded,
            # weights stay at the last coordinated checkpoint.
            if master or saves_shards:
                try:
                    self.serialize(sync_flush=True)
                except Exception:  # noqa: BLE001
                    log.exception("Failed to persist error status")
            # Best-effort join of the train-end fence so healthy peers are
            # released promptly instead of eating the full barrier timeout
            # waiting for this (failed) host.  Short timeout: if the peers
            # are themselves far from the barrier, give up and let the
            # original error surface.
            try:
                dist.barrier(f"train_end_{self.model_id}_{train_seq}",
                             timeout_s=60.0)
            except Exception:  # noqa: BLE001
                log.warning("train-end barrier join from error path "
                            "failed", exc_info=True)
            raise

    def _record_overall_progress(self, last_batch):
        """Fold the run's progress into the overall average-cost history and
        refresh /stats/ (reference ``_record_training_overall_progress``,
        neural_net_model.py:724-733)."""
        import random
        if self.progress:
            avg_progress_cost = (sum(p["cost"] for p in self.progress)
                                 / len(self.progress))
            self.avg_cost = ((self.avg_cost or avg_progress_cost)
                             + avg_progress_cost) / 2.0
            self.avg_cost_history.append(self.avg_cost)
            if len(self.avg_cost_history) > 100:
                self.avg_cost_history.pop(random.randint(1, 98))
        if last_batch is not None:
            self.stats = self._compute_stats(*last_batch)

    def _train_epoch_microstepped(self, xs, ys, call_rng, num_steps: int, *,
                                  remat, compute_dtype, sp_mesh,
                                  out_shardings, sp_mode, ep_mesh,
                                  with_ratios: bool):
        """Decode-priority epoch: one device program per micro-step, with a
        priority window (:func:`_yield_to_decodes`) opened between them so
        pending ``/generate/`` dispatches interleave at micro-step
        granularity (see ``CompiledArch.train_micro_fns`` for the numerics
        contract)."""
        micro_fn, finalize_fn = self.arch.train_micro_fns(
            self.optimizer_config, num_steps, remat=remat,
            compute_dtype=compute_dtype, sp_mesh=sp_mesh,
            platform=self._placement, with_ratios=with_ratios,
            out_shardings=out_shardings, sp_mode=sp_mode, ep_mesh=ep_mesh)
        grads = _sharded_zero_grads(self.params)
        cost = self.arch.zero_cost_sum()
        bufs = self.buffers
        for i in range(num_steps):
            if i:
                _yield_to_decodes()
            bufs, grads, cost = micro_fn(self.params, bufs, grads, cost,
                                         xs[i], ys[i], call_rng, i)
        return finalize_fn(self.params, self.opt_state, grads, bufs, cost)

    def _training_mesh(self, micro_batch: int, block_size: int):
        """Device mesh for the training run (None = single device).

        ``micro_batch`` is the per-process rows of one micro-step —
        ``batch_size`` under the reference's buffer semantics.
        Data-parallelism over every local device is automatic when the
        micro-batch divides the data axis; ``PENROZ_MESH_MODEL`` /
        ``PENROZ_MESH_SEQUENCE`` / ``PENROZ_MESH_EXPERT`` carve tensor/
        sequence/expert-parallel axes out of the same device set, and
        ``PENROZ_TRAIN_MESH=0`` disables meshing (single-process only).
        This replaces the reference's per-request DDP process tree
        (ddp.py:38-73) — the mesh lives inside one compiled program.
        """
        if os.environ.get("PENROZ_TRAIN_MESH", "1") == "0":
            if dist.process_count() > 1:
                # Opting out of the mesh under multi-host would train
                # divergent per-host replicas with no gradient sync while
                # the loader still rank-strides the data — silent
                # corruption, so refuse loudly.
                raise RuntimeError(
                    "PENROZ_TRAIN_MESH=0 is invalid when "
                    f"process_count={dist.process_count()} > 1: multi-host "
                    "training requires the global mesh for gradient sync")
            return None
        if dist.process_count() > 1:
            return self._multihost_mesh(micro_batch, block_size)
        return self._local_mesh(micro_batch, block_size, fold_pipe=False)

    def _local_mesh(self, micro_batch: int, block_size: int, *,
                    fold_pipe: bool):
        """Single-host mesh from the ``PENROZ_MESH_*`` env family (None =
        single device).  ``fold_pipe=True`` folds the pipe axis into
        ``data`` (forward-only callers: no pipeline schedule to run, so
        the pipe-stage chips serve as extra data-parallel capacity);
        ``fold_pipe=False`` keeps it as a mesh axis.
        """
        try:
            platform = self.device.platform if self.device is not None else None
            devices = (jax.local_devices(backend=platform) if platform
                       else jax.local_devices())
        except RuntimeError:
            return None
        try:
            model = int(os.environ.get("PENROZ_MESH_MODEL", "1"))
            seq = int(os.environ.get("PENROZ_MESH_SEQUENCE", "1"))
            expert = int(os.environ.get("PENROZ_MESH_EXPERT", "1"))
            pipe = int(os.environ.get("PENROZ_MESH_PIPE", "1"))
        except ValueError:
            log.warning("Invalid PENROZ_MESH_MODEL/PENROZ_MESH_SEQUENCE/"
                        "PENROZ_MESH_EXPERT/PENROZ_MESH_PIPE; falling back "
                        "to single device")
            return None
        if model < 1 or seq < 1 or expert < 1 or pipe < 1:
            return None
        if fold_pipe:
            pipe = 1
        else:
            _check_pipe_composition(pipe, seq)
        n = len(devices)
        if n <= 1 or n % (model * seq * expert * pipe):
            return None
        data = n // (model * seq * expert * pipe)
        if micro_batch % data or (seq > 1 and block_size % seq):
            # WARNING, not INFO: on a multi-chip host this line decides
            # whether all but one chip sit idle for the whole run.
            log.warning("Mesh fallback to single device (%d of %d devices "
                        "idle): micro-batch %d / block %d not divisible by "
                        "data=%d / sequence=%d", n - 1, n, micro_batch,
                        block_size, data, seq)
            return None
        return mesh_lib.make_mesh(devices, model=model, sequence=seq,
                                  expert=expert, pipe=pipe)

    def _eval_mesh(self, batch_size: int, block_size: int):
        """Device mesh for forward-only evaluation (None = single device).

        Same axes as :meth:`_training_mesh` except the ``pipe`` axis is
        folded into ``data``.  Falls back to a single device (never
        raises) on divisibility misses single-host; the multi-host path
        keeps :meth:`_multihost_mesh`'s raise-don't-degrade contract.
        """
        if os.environ.get("PENROZ_TRAIN_MESH", "1") == "0":
            # Unlike training, the mesh-less multi-host eval is still
            # exact: each process averages its own stride and
            # all_reduce_mean combines them — no gradient sync to lose.
            return None
        if dist.process_count() > 1:
            return self._multihost_mesh(batch_size, block_size,
                                        fold_pipe=True)
        return self._local_mesh(batch_size, block_size, fold_pipe=True)

    def _multihost_mesh(self, micro_batch: int, block_size: int = 0,
                        fold_pipe: bool = False):
        """Global mesh spanning every host's devices.

        The data axis is ordered by process (jax.devices() groups by
        process_index), so each host's rank-strided loader rows land on its
        own chips.  PENROZ_MESH_MODEL / PENROZ_MESH_SEQUENCE /
        PENROZ_MESH_EXPERT carve TP/SP/EP axes out of the global device set;
        the resulting cross-host-sharded params/optimizer are persisted via
        per-host shard files (see :meth:`serialize`).

        ``PENROZ_MESH_PIPE>1`` builds the pipe axis *outermost* so each
        GPipe stage occupies a contiguous host group and the stage handoff
        rides DCN (``fold_pipe=True`` — forward-only callers — folds it
        into data capacity instead).  Stages spanning hosts means every
        process feeds the SAME batch (the model, not the data, is what's
        distributed across hosts); train() switches the loader off rank
        striding accordingly.
        """
        world = dist.process_count()
        # Every failure here RAISES: falling back to mesh=None under
        # multi-process would train divergent per-host replicas with no
        # gradient sync while the loader still stripes the data — silent
        # corruption, not degradation.
        platform = self.device.platform if self.device is not None else None
        devices = jax.devices(platform) if platform else jax.devices()
        n = len(devices)
        if n % world:
            raise RuntimeError(f"multi-host training: {n} global devices "
                               f"not divisible by {world} processes")
        try:
            model = int(os.environ.get("PENROZ_MESH_MODEL", "1"))
            seq = int(os.environ.get("PENROZ_MESH_SEQUENCE", "1"))
            expert = int(os.environ.get("PENROZ_MESH_EXPERT", "1"))
        except ValueError as e:
            raise ValueError(f"Invalid mesh-axis env knob: {e}")
        try:
            pipe = int(os.environ.get("PENROZ_MESH_PIPE", "1") or "1")
        except ValueError as e:
            raise ValueError(f"Invalid mesh-axis env knob: {e}")
        if pipe < 1:
            raise ValueError(f"PENROZ_MESH_PIPE={pipe} must be >= 1")
        if fold_pipe:
            pipe = 1
        if pipe > 1:
            _check_pipe_composition(pipe, seq)
            if pipe % world and world % pipe:
                # Stages are contiguous global device ranges (pipe
                # outermost); alignment with process boundaries keeps each
                # ppermute hop a single DCN (or pure-ICI) transfer instead
                # of a shuffle that splits one stage across host fractions.
                raise RuntimeError(
                    f"PENROZ_MESH_PIPE={pipe} must divide or be a multiple "
                    f"of the process count ({world}) so pipeline stages "
                    f"align with host boundaries")
        denom = model * seq * expert * pipe
        if model < 1 or seq < 1 or expert < 1 or n % denom:
            raise ValueError(
                f"multi-host training: {n} global devices not divisible by "
                f"model={model} × sequence={seq} × expert={expert} × "
                f"pipe={pipe}")
        data = n // denom
        if pipe > 1:
            # Every process feeds the same global batch (no rank striding
            # — see train()); the data axis shards those rows within each
            # stage's host group.
            if micro_batch % data:
                raise ValueError(
                    f"multi-host training: batch_size {micro_batch} must "
                    f"be divisible by the data axis ({data}) under "
                    f"PENROZ_MESH_PIPE={pipe}")
        elif (micro_batch * world) % data:
            raise ValueError(
                f"multi-host training: global micro-batch "
                f"{micro_batch * world} (batch_size × processes) must be "
                f"divisible by the data axis ({data})")
        if seq > 1 and block_size and block_size % seq:
            raise ValueError(
                f"multi-host training: block_size {block_size} must be "
                f"divisible by the sequence axis ({seq})")
        return mesh_lib.make_mesh(devices, model=model, sequence=seq,
                                  expert=expert, pipe=pipe,
                                  pipe_outermost=pipe > 1)

    # -- pipeline-parallel training layout ----------------------------------

    def _enter_pipe_layout(self, mesh, batch_size: int):
        """Switch params/opt_state to the GPipe stacked layout.

        The repeated transformer blocks' per-layer params
        ``layers.{i}.<suffix>`` become ``__pipe__.<suffix>`` leaves with a
        leading ``(L, ...)`` dim sharded over the mesh's ``pipe`` axis —
        each stage physically holds only its ``L/P`` blocks (the depth
        analog of TP's width sharding).  Optimizer moment dicts get the
        identical restructuring so the elementwise update math lines up.
        The checkpoint format stays canonical flat: :meth:`serialize`
        converts back via :meth:`_canonical_state`.

        Returns ``(pipe_cfg, epoch_out_shardings)`` where ``pipe_cfg =
        (mesh, start, count, num_microbatches)`` feeds
        :meth:`CompiledArch.train_epoch_fn`.
        """
        self.arch.refuse_looped("pipeline stages (PENROZ_MESH_PIPE)")
        from penroz_tpu.parallel import pipeline
        pipe = mesh.shape[mesh_lib.PIPE_AXIS]
        data = mesh.shape[mesh_lib.DATA_AXIS]
        # ZeRO ladder over the stacked layout: PENROZ_WUS=1 data-shards
        # the optimizer moments on a dim the pipe/TP layout leaves free;
        # PENROZ_FSDP=1 shards the stacked params' storage the same way —
        # gpipe_apply's shard_map in_spec (P(pipe), replicated over data)
        # then forces a just-in-time all-gather at the schedule boundary,
        # and its AD transpose reduce-scatters the gradients: ZeRO-3
        # semantics from the resharding rule, no bespoke gather code.
        fsdp = os.environ.get("PENROZ_FSDP", "0") == "1"
        wus = fsdp or os.environ.get("PENROZ_WUS", "0") == "1"
        start, count = pipeline.pipeline_block_range(self.layers_dsl)
        if count < pipe or count % pipe:
            raise RuntimeError(
                f"PENROZ_MESH_PIPE={pipe}: the longest run of identical "
                f"blocks is {count} (need a multiple of the pipe axis); "
                f"this DSL cannot pipeline at that depth")
        # MoE blocks pipeline: balance loss + router fractions travel the
        # schedule's aux channel (gpipe_apply with_aux).  BatchNorm stays
        # refused — its running stats are read AND written per microbatch,
        # a sequential dependency the parallel schedule cannot honor.
        seq = mesh.shape[mesh_lib.SEQ_AXIS]
        if seq > 1 and any(
                jnp.issubdtype(v.dtype, jnp.floating)
                and v.dtype != jnp.float32 for v in self.params.values()):
            # XLA CHECK-fails ("Invalid binary instruction opcode copy",
            # hlo_instruction.cc) compiling the manual pipe×seq program
            # with bf16 parameter leaves — an UNCATCHABLE process abort,
            # reproduced on the CPU backend with a minimal rope stack.
            # Refuse until the toolchain moves; fp32 storage (the
            # non-imported default) is unaffected.
            raise RuntimeError(
                "PENROZ_MESH_PIPE>1 with PENROZ_MESH_SEQUENCE>1 requires "
                "float32 parameter storage (bf16-imported models trip an "
                "XLA compiler abort on this composition); convert the "
                "model or drop one axis")
        for i in range(start, start + count):
            for sub in self.arch.mods[i].walk():
                if isinstance(sub, M.BatchNorm1d):
                    raise RuntimeError(
                        f"PENROZ_MESH_PIPE>1 cannot pipeline blocks with "
                        f"{type(sub).__name__}: running statistics are "
                        f"read and written per microbatch, which the "
                        f"parallel schedule cannot order")
                if seq > 1 and isinstance(sub, M.CausalSelfAttention):
                    if sub.dropout > 0.0:
                        # The manual SP branch (ring or Ulysses)
                        # requires dropout-free attention (same constraint
                        # as the sp_mesh path), but here falling through
                        # would run SHARD-LOCAL attention — silently
                        # wrong, so refuse.
                        raise RuntimeError(
                            "PENROZ_MESH_PIPE>1 with PENROZ_MESH_SEQUENCE"
                            ">1 cannot pipeline attention with dropout>0: "
                            "the sequence-parallel attention path is "
                            "dropout-free")
        base = batch_size // data
        env_m = os.environ.get("PENROZ_PIPE_MICROBATCHES", "")
        if env_m:
            micro = int(env_m)
            if micro < 1 or base % micro:
                raise RuntimeError(
                    f"PENROZ_PIPE_MICROBATCHES={micro} must divide the "
                    f"per-data-shard batch ({base})")
        else:
            # GPipe bubble is (P-1)/(M+P-1): aim for M ≈ 4P, constrained
            # to divide the per-data-shard batch so rows split evenly.
            target = min(base, 4 * pipe)
            micro = next(m for m in range(target, 0, -1) if base % m == 0)
        idx = list(range(start, start + count))
        stacked = pipeline.stack_block_params(self.params, idx)
        block_keys = {f"layers.{i}.{s}" for i in idx for s in stacked}
        mixed = {k: v for k, v in self.params.items() if k not in block_keys}
        mixed.update({f"__pipe__.{s}": v for s, v in stacked.items()})
        pkeys = set(self.params)

        def mix(d: dict) -> dict:
            st = pipeline.stack_block_params(d, idx)
            out = {k: v for k, v in d.items() if k not in block_keys}
            out.update({f"__pipe__.{s}": v for s, v in st.items()})
            return out

        opt_mixed = jax.tree.map(
            lambda n: mix(n) if isinstance(n, dict) and set(n) == pkeys
            else n,
            self.opt_state,
            is_leaf=lambda n: isinstance(n, dict) and set(n) == pkeys)
        repl = mesh_lib.replicated(mesh)

        def pipe_spec(suffix: str):
            # Stacked leaves: leading L dim over `pipe`, trailing dims in
            # the Megatron TP layout of the per-layer leaf (a no-op spec
            # when the model axis is 1) — this is what lets pipe×model
            # meshes train; gpipe_apply leaves the model axis
            # GSPMD-automatic inside the stage body.
            base = sharding_lib.param_spec(
                f"layers.{idx[0]}.{suffix}",
                tuple(stacked[suffix].shape[1:]), mesh)
            return jax.sharding.PartitionSpec(mesh_lib.PIPE_AXIS, *base)

        base_spec = {}
        for k, v in mixed.items():
            if k.startswith("__pipe__."):
                base_spec[k] = pipe_spec(k[len("__pipe__."):])
            else:
                # Non-block params (embeddings, final LN, lm head) take
                # their flat TP layout; replicated when model == 1.
                base_spec[k] = sharding_lib.param_spec(k, tuple(v.shape),
                                                       mesh)

        def with_data(k):
            # ZeRO rule: data axis on the first dim the pipe/TP layout
            # leaves free (sharding._data_axis_spec; no-op when data==1
            # or no dim divides).
            return sharding_lib._data_axis_spec(
                base_spec[k], tuple(mixed[k].shape), mesh)

        param_shd = {k: jax.sharding.NamedSharding(
                         mesh, with_data(k) if fsdp else base_spec[k])
                     for k in mixed}
        moment_shd = {k: jax.sharding.NamedSharding(
                          mesh, with_data(k) if wus else base_spec[k])
                      for k in mixed}
        opt_shd = jax.tree.map(
            lambda n: ({k: moment_shd[k] for k in n}
                       if isinstance(n, dict) and set(n) == set(mixed)
                       else repl),
            opt_mixed,
            is_leaf=lambda n: isinstance(n, dict) and set(n) == set(mixed))
        self.params = {k: sharding_lib.place(v, param_shd[k])
                       for k, v in mixed.items()}
        self.opt_state = sharding_lib.place_tree(opt_mixed, opt_shd)
        self._pipe_layout = (start, count)
        log.info("Pipeline layout: blocks %d..%d stacked over pipe=%d, "
                 "%d microbatch(es)%s", start, start + count - 1, pipe,
                 micro,
                 " + FSDP" if fsdp else (" + WUS" if wus else ""))
        return (mesh, start, count, micro), (param_shd, opt_shd)

    def _canonical_params(self, params=None) -> dict:
        """Flat per-layer param dict regardless of an active pipeline
        layout (the canonical checkpoint/serving key naming)."""
        from penroz_tpu.parallel import pipeline
        params = self.params if params is None else params
        if self._pipe_layout is None:
            return params
        start, count = self._pipe_layout
        idx = list(range(start, start + count))
        stacked = {k[len("__pipe__."):]: v for k, v in params.items()
                   if k.startswith("__pipe__.")}
        flat = {k: v for k, v in params.items()
                if not k.startswith("__pipe__.")}
        flat.update(pipeline.unstack_block_params(stacked, idx))
        return flat

    def _canonical_state(self):
        """(params, opt_state) in the canonical flat layout."""
        if self._pipe_layout is None:
            return self.params, self.opt_state
        mixed_keys = set(self.params)
        opt = jax.tree.map(
            lambda n: (self._canonical_params(n)
                       if isinstance(n, dict) and set(n) == mixed_keys
                       else n),
            self.opt_state,
            is_leaf=lambda n: isinstance(n, dict) and set(n) == mixed_keys)
        return self._canonical_params(), opt

    def _exit_pipe_layout(self, local_only: bool = False):
        """Restore the canonical flat layout after a pipelined train run.

        ``local_only=True`` (the error path, where hosts arrive
        independently): skip the conversion when stacked leaves are
        cross-host sharded — unstacking them is a collective, and running
        it one-sided would hang until the comm timeout.  The model object
        keeps its stacked layout; the next operation reloads from the last
        coordinated checkpoint.
        """
        if self._pipe_layout is None:
            return
        if local_only and not all(self._is_host_readable(v)
                                  for v in self.params.values()):
            log.warning("Keeping pipeline-stacked layout: cross-host "
                        "shards cannot be restored one-sidedly")
            return
        self.params, self.opt_state = self._canonical_state()
        self._pipe_layout = None

    @classmethod
    def train_model_on_device(cls, model_id, device, dataset_id, shard,
                              epochs, batch_size, block_size, step_size,
                              adapter=None, trace=None):
        """Worker entry: deserialize → place → train (reference DDP worker:
        neural_net_model.py:516-550, minus the process tree — one process
        owns the TPU runtime and the mesh handles per-chip parallelism).

        ``PENROZ_TRAIN_WORKER=1`` (single-host only) instead trains in a
        CHILD process — the reference's crash-containment shape
        (main.py:461-464 spawns ``mp.Process``): a native crash in
        training (XLA abort, OOM kill, libtpu segfault) kills the worker,
        never the serving process.  State flows through the existing
        checkpoint stream (the worker serializes every ~10s; every API
        route deserializes), so /progress/ and /stats/ keep updating
        while the worker runs.  Caveat: a TPU chip belongs to one process
        — worker mode fits CPU-placed training, or a server that has not
        touched the chip (it serves from the CPU or another chip); a
        worker asked for a chip its parent holds ends in status ``Error``
        (here when the device cannot be resolved, in the parent's
        post-mortem when the runtime would not start at all).  It is
        opt-in for exactly that reason.

        ``trace`` (``utils/tracing.py``, started by ``PUT /train/``) is
        made current for this thread, so the job's spans land in it, and
        is finished here with the job's end status.  A child process
        records nothing into it, and the trace says so.
        """
        try:
            with tracing.use(trace):
                model = cls._train_on_device(
                    model_id, device, dataset_id, shard, epochs,
                    batch_size, block_size, step_size, adapter, trace)
        except BaseException as e:
            if trace is not None:
                trace.annotate(status="Error", error=str(e))
                trace.finish("error")
            raise
        if trace is not None:
            code = model.status.get("code")
            trace.annotate(status=code)
            trace.finish("error" if code == "Error" else "completed")
        return model

    @classmethod
    def _train_on_device(cls, model_id, device, dataset_id, shard, epochs,
                         batch_size, block_size, step_size, adapter, trace):
        if (os.environ.get("PENROZ_TRAIN_WORKER", "0") == "1"
                and dist.process_count() == 1):
            if trace is not None:
                trace.annotate(recorded=False, note=(
                    "PENROZ_TRAIN_WORKER=1: the job ran in a child process; "
                    "spans are recorded in the training process only"))
            return cls._train_in_worker_process(
                model_id, device, dataset_id, shard, epochs, batch_size,
                block_size, step_size, adapter=adapter)
        with tracing.span("penroz/train_setup") as setup_span:
            model = cls.deserialize(model_id)
            try:
                model.to_device(device)
            except ValueError as e:
                # /train/ validated the string in the serving process;
                # failing here means THIS process (a worker child) cannot
                # reach the device — record it where /progress/ polls will
                # see it (an adapter run leaves the base status alone; the
                # parent's post-mortem logs its death).
                if adapter is None:
                    model.status = {"code": "Error", "message": str(e)}
                    model.serialize(sync_flush=True)
                raise
            log.info("Training model %s on %s", model_id,
                     model.device if model.device is not None
                     else f"default placement ({jax.default_backend()})")
            if adapter is None:
                # closes setup_span itself, before the first epoch
                model.train_model(dataset_id, shard=shard, epochs=epochs,
                                  batch_size=batch_size,
                                  block_size=block_size,
                                  step_size=step_size,
                                  setup_span=setup_span)
                return model
        # LoRA fine-tune: the base stays frozen, only the adapter tree
        # trains, and the checkpoint written is adapter-only
        # (models/lora.py) — registry-loadable the moment it lands.
        from penroz_tpu.models import lora
        lora.train_adapter(model, adapter["adapter_id"], adapter,
                           dataset_id, shard=shard, epochs=epochs,
                           batch_size=batch_size, block_size=block_size,
                           step_size=step_size)
        return model

    @classmethod
    def _train_in_worker_process(cls, model_id, device, dataset_id, shard,
                                 epochs, batch_size, block_size, step_size,
                                 adapter=None):
        """Run the training job in a subprocess and contain its crashes.

        The parent blocks (callers already run this on an executor
        thread), watches the worker, and post-mortems the checkpoint: a
        worker that died mid-run leaves status ``Training`` behind, which
        the parent rewrites to ``Error`` — the same contract as the
        startup orphan sweep (serve/app.py::_sweep_orphaned_training),
        applied the moment the death is observed instead of at the next
        restart."""
        import subprocess
        import sys
        args = {"model_id": model_id, "device": device,
                "dataset_id": dataset_id, "shard": shard, "epochs": epochs,
                "batch_size": batch_size, "block_size": block_size,
                "step_size": step_size, "adapter": adapter}
        env = dict(os.environ)
        env.pop("PENROZ_TRAIN_WORKER", None)  # the child trains in-process
        from penroz_tpu.utils import checkpoint
        env["PENROZ_SHM_PATH"] = checkpoint.SHM_PATH
        # The child runs in the parent's cwd (model/data folders are
        # relative), which need not contain the package — resolve imports
        # from this install's location.
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        prev = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = repo + (os.pathsep + prev if prev else "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "penroz_tpu.models.train_worker",
             json.dumps(args)], env=env, cwd=os.getcwd())
        _TRAIN_WORKERS[model_id] = proc
        try:
            rc = proc.wait()
        finally:
            _TRAIN_WORKERS.pop(model_id, None)
        if adapter is not None:
            cls._post_mortem_adapter_worker(adapter["adapter_id"], rc)
            return cls.deserialize(model_id)
        model = cls.deserialize(model_id)
        if rc != 0 and model.status.get("code") != "Error":
            # Died mid-run (status still Training) or before it recorded
            # anything at all (the runtime would not start in the child,
            # e.g. the parent holds the only chip).
            log.error("Training worker for model %s died (rc=%s); marking "
                      "Error", model_id, rc)
            model.status = {
                "code": "Error",
                "message": f"Training worker died (rc={rc}); last "
                           f"checkpoint retained"}
            model.serialize(sync_flush=True)
        elif rc != 0:
            # Clean Python-level failure: the child already recorded status
            # Error and exited 1 — the parent still logs the death so a
            # fleet operator sees it without polling /progress/.
            log.error("Training worker for model %s exited rc=%s "
                      "(status %s)", model_id, rc,
                      model.status.get("code"))
        return model

    @staticmethod
    def _post_mortem_adapter_worker(adapter_id: str, rc: int):
        """Adapter-run analog of the base post-mortem: a worker that died
        mid-run leaves the ADAPTER blob saying 'Training' — rewrite it to
        Error; a clean failure (status already Error, rc=1) is logged."""
        try:
            blob = checkpoint.load_adapter(adapter_id)
        except KeyError:
            if rc != 0:
                log.error("Adapter-training worker for %s died (rc=%s) "
                          "before writing any checkpoint", adapter_id, rc)
            return
        code = (blob.get("status") or {}).get("code")
        if rc != 0 and code == "Training":
            log.error("Adapter-training worker for %s died (rc=%s); "
                      "marking Error", adapter_id, rc)
            blob["status"] = {
                "code": "Error",
                "message": f"Training worker died (rc={rc}); last "
                           f"checkpoint retained"}
            checkpoint.save_adapter(adapter_id, blob, sync_flush=True)
        elif rc != 0:
            log.error("Adapter-training worker for %s exited rc=%s "
                      "(status %s)", adapter_id, rc, code)

    def _compute_stats(self, x, y) -> dict:
        """/stats/ histograms from one host-local micro-batch.

        Under multi-host the params are global arrays spanning hosts; the
        instrumented pass runs process-locally on this host's copy of the
        (replicated) params with its local sub-batch — the reference always
        produces stats on master (neural_net_model.py:705-709), so a
        master-local sample preserves the feature instead of skipping it.
        """
        # Raw-layout readability check BEFORE the canonical conversion:
        # with a pipeline-stacked layout active on a multi-host mesh, the
        # unstack is itself a collective and stats run master-only — a
        # one-sided dispatch would hang against peers that never join.
        if any(not self._is_host_readable(v)
               for v in self.params.values()):
            log.info("Skipping stats capture: params sharded across hosts")
            return self.stats
        params, buffers = self._canonical_params(), self.buffers
        if any(not getattr(v, "is_fully_addressable", True)
               for v in params.values()):
            if not all(getattr(v, "is_fully_replicated", True)
                       for v in params.values()):
                log.info("Skipping stats capture: params sharded across "
                         "hosts")
                return self.stats
            dev = jax.local_devices()[0]
            params = {k: jax.device_put(np.asarray(v), dev)
                      for k, v in params.items()}
            buffers = {k: jax.device_put(np.asarray(v), dev)
                       for k, v in buffers.items()}
        acts, act_grads, weight_grads = self.arch.stats_grads(
            params, buffers, x, y, platform=self._placement)
        acts_np = [np.asarray(a, np.float32) for a in acts]
        grads_np = [np.asarray(g, np.float32) for g in act_grads]
        weights = [np.asarray(params[k], np.float32)
                   for k in self.arch.param_order]
        wgrads = [np.asarray(weight_grads[k], np.float32)
                  for k in self.arch.param_order]
        return stats_lib.build_stats(self.arch.algos, acts_np, grads_np,
                                     weights, wgrads)

    # -- generation ---------------------------------------------------------

    def _kv_dtype(self):
        dt = self.dtype
        return dt if jnp.issubdtype(dt, jnp.floating) else jnp.float32

    def _decode_mesh(self, batch: int = 1):
        """Device mesh for generation (None = single-device decode).

        TP-sharded decode: attention-head K/V buffers and the Megatron
        weight layout shard over ``model``, stacked MoE expert weights
        over ``expert``, sampling replicated — so an imported model larger
        than one chip's HBM can *serve*, not just train/evaluate
        (reference decode is single-device too: neural_net_model.py:
        360-406; this is the beyond-parity axis).  A single stream has no
        data axis; the BATCHED path additionally shards rows over ``data``
        when ``PENROZ_DECODE_DP=1`` and the batch divides the leftover
        devices (throughput scaling for /generate_batch/ — opt-in so
        multi-device hosts don't silently change decode placement).
        Gated to the contiguous fp/bf16 cache — the paged and int8
        layouts keep single-device decode (their block tables and scale
        planes have no mesh layout yet).
        """
        if dist.process_count() > 1:
            return None  # serving is per-host; the API serves local chips
        if os.environ.get("PENROZ_TRAIN_MESH", "1") == "0":
            return None
        if KV.paged_enabled() or KV.turbo_quant_enabled():
            return None
        try:
            model = int(os.environ.get("PENROZ_MESH_MODEL", "1"))
            expert = int(os.environ.get("PENROZ_MESH_EXPERT", "1"))
        except ValueError:
            log.warning("Invalid PENROZ_MESH_MODEL/PENROZ_MESH_EXPERT; "
                        "falling back to single-device decode")
            return None
        if model < 1 or expert < 1:
            return None
        try:
            platform = (self.device.platform if self.device is not None
                        else None)
            devices = (jax.local_devices(backend=platform) if platform
                       else jax.local_devices())
        except RuntimeError:
            return None
        if len(devices) < model * expert:
            return None
        dp = 1
        if (batch > 1
                and os.environ.get("PENROZ_DECODE_DP", "0") == "1"):
            leftover = len(devices) // (model * expert)
            dp = next((d for d in range(min(leftover, batch), 0, -1)
                       if batch % d == 0), 1)
        if model * expert * dp <= 1:
            return None
        return mesh_lib.make_mesh(devices[:model * expert * dp],
                                  model=model, expert=expert)

    def _kv_sharding_tree(self, kv, mesh, batch: int = 1):
        """Sharding pytree for a contiguous KVState: (B, Hkv, S, D) leaves
        shard heads over ``model`` when every attention layer's KV head
        count divides the axis (GQA models with few KV heads stay
        replicated — a torn head is worse than a copied cache) and rows
        over ``data`` when the batch divides it; lengths and scalars
        replicate."""
        from jax.sharding import PartitionSpec as P
        tp = mesh.shape[mesh_lib.MODEL_AXIS]
        dp = mesh.shape[mesh_lib.DATA_AXIS]
        heads_ok = all(h % tp == 0 for h, _ in self.arch.kv_specs)
        # Row sharding stays behind the PENROZ_DECODE_DP opt-in even here:
        # the live branch hands this a TRAINING mesh whose data axis the
        # decode-mesh gate never saw, and rows silently sharding over it
        # is exactly the placement surprise the opt-in exists to prevent.
        dp_rows = (dp > 1 and batch % dp == 0
                   and os.environ.get("PENROZ_DECODE_DP", "0") == "1")
        kv_spec = P(mesh_lib.DATA_AXIS if dp_rows else None,
                    mesh_lib.MODEL_AXIS if heads_ok and tp > 1 else None,
                    None, None)

        def leaf_sharding(leaf):
            spec = kv_spec if getattr(leaf, "ndim", 0) == 4 else P()
            return jax.sharding.NamedSharding(mesh, spec)

        return jax.tree.map(leaf_sharding, kv)

    def _enter_decode_mesh(self, kv, batch: int = 1):
        """Place params/buffers/cache for mesh decode; returns the placed
        cache (identity when no decode mesh is configured)."""
        mesh = self._decode_mesh(batch)
        if mesh is None:
            return kv
        if any(k.startswith("__pipe__") for k in self.params):
            return kv  # mid-pipeline-training layout: leave decode alone
        live = [v for v in self.params.values()
                if isinstance(getattr(v, "sharding", None),
                              jax.sharding.NamedSharding)
                and len(v.sharding.device_set) > 1]
        if live:
            # Params already live on a (training/eval) mesh — do NOT
            # reshard them: gathering ZeRO-3 storage onto the decode
            # submesh could OOM the exact models FSDP exists for, and a
            # decode interleaving with mesh training would flip layouts
            # every time (full param copy + micro-step recompile).  GSPMD
            # decodes fine on the existing layout; only the fresh KV
            # cache follows that mesh.
            return jax.device_put(
                kv, self._kv_sharding_tree(kv, live[0].sharding.mesh,
                                           batch))
        log.info("Generating over device mesh %s", dict(mesh.shape))
        self.params = sharding_lib.shard_params(self.params, mesh)
        self.buffers = {
            k: sharding_lib.place(v, mesh_lib.replicated(mesh))
            for k, v in self.buffers.items()}
        return jax.device_put(kv, self._kv_sharding_tree(kv, mesh, batch))

    def _serve_mesh(self, replica: int = 0):
        """Serving mesh for a continuous-batching DecodeEngine (None =
        single-device, today's layout).  Opt-in via ``PENROZ_SERVE_MESH=1``
        with ``PENROZ_SERVE_MESH_MODEL`` tensor-parallel devices per
        router replica (``replica`` picks which — parallel/mesh.py) — unlike
        :meth:`_decode_mesh` this path DOES cover the paged and int8
        layouts (the page pools shard their head dim; block tables and
        allocator counters stay replicated, the scheduler keeps authoring
        them host-side)."""
        if os.environ.get("PENROZ_SERVE_MESH", "0") != "1":
            return None
        if dist.process_count() > 1:
            return None  # engines are per-host; scale-out is the router
        try:
            model = int(os.environ.get("PENROZ_SERVE_MESH_MODEL", "1"))
        except ValueError:
            log.warning("Invalid PENROZ_SERVE_MESH_MODEL; serving "
                        "single-device")
            return None
        if model < 1:
            return None
        try:
            platform = (self.device.platform if self.device is not None
                        else None)
            devices = (jax.local_devices(backend=platform) if platform
                       else jax.local_devices())
        except RuntimeError:
            return None
        if len(devices) < model:
            log.warning("PENROZ_SERVE_MESH_MODEL=%d exceeds %d local "
                        "devices; serving single-device", model,
                        len(devices))
            return None
        return mesh_lib.serve_mesh(model=model, devices=devices,
                                   replica=replica)

    def enter_serve_mesh(self, kv, pipe=None, replica: int = 0):
        """Place params/buffers and a DecodeEngine's freshly allocated KV
        state on the serving mesh (``PENROZ_SERVE_MESH=1``).  Returns
        ``(kv, devices)`` where ``devices`` is the mesh size (1 when
        unmeshed).  A 1-device mesh is numerically a GSPMD no-op —
        token-identical to the unmeshed engine — which is what lets the
        CPU tier-1 parity matrix keep proving correctness for the sharded
        serving path.

        A model still in the ``__pipe__`` stacked layout from a pipelined
        train run is restored to the canonical flat layout first (the
        decode programs address ``layers.{i}.*``) — serving no longer
        refuses the layout; only cross-host stacked shards (where the
        unstack would be a one-sided collective) are left alone.

        ``pipe`` (a :class:`ServePipeline`) switches to stage-partitioned
        placement: each stage's params/buffers and its slice of the paged
        pools land on that stage's own mesh
        (``parallel.mesh.serve_stage_meshes`` ×
        ``PENROZ_SERVE_MESH_MODEL`` TP width per stage)."""
        if any(k.startswith("__pipe__") for k in self.params):
            if all(self._is_host_readable(v)
                   for v in self.params.values()):
                log.info("Restoring flat layer layout from __pipe__ "
                         "stacked params for serving")
                self._exit_pipe_layout()
            else:
                return kv, 1  # cross-host stacked shards: leave alone
        if pipe is not None:
            return self._enter_serve_pipe_mesh(kv, pipe, replica)
        mesh = self._serve_mesh(replica)
        if mesh is None:
            if replica and jax.local_device_count() > 1:
                log.warning(
                    "serving replica %d shares the default device with "
                    "replica 0 (%d local devices); PENROZ_SERVE_MESH=1 "
                    "gives each replica its own", replica,
                    jax.local_device_count())
            return kv, 1
        live = [v for v in self.params.values()
                if isinstance(getattr(v, "sharding", None),
                              jax.sharding.NamedSharding)
                and len(v.sharding.device_set) > 1]
        if live:
            # Same rule as _enter_decode_mesh: params already living on a
            # multi-device (training/eval) mesh are NOT reshuffled —
            # gathering ZeRO-3 storage could OOM the exact models FSDP
            # exists for.  The engine's KV simply follows that mesh.
            mesh = live[0].sharding.mesh
        else:
            log.info("Serving over device mesh %s", dict(mesh.shape))
            self.params = sharding_lib.shard_params(self.params, mesh)
            self.buffers = {
                k: sharding_lib.place(v, mesh_lib.replicated(mesh))
                for k, v in self.buffers.items()}
        if isinstance(kv, KV.PagedKVState):
            tree = sharding_lib.paged_kv_sharding_tree(
                kv, mesh, self.arch.kv_specs)
        else:
            tree = self._kv_sharding_tree(kv, mesh)
        return jax.device_put(kv, tree), mesh.size

    def _enter_serve_pipe_mesh(self, kv, pipe, replica: int = 0):
        """Stage-partitioned placement for one pipeline group: stage ``s``
        gets its params/buffers sharded over its own TP mesh and its
        ``kv_bounds[s]`` slice of the paged pools placed there
        (parallel/sharding.py::paged_kv_stage_shard) — per-device KV HBM
        drops ~1/S.  On a host with fewer devices than ``stages × model``
        every stage collapses onto the same devices: the partition,
        schedule and numerics are identical and placement is skipped (the
        CPU parity suite rides this degenerate layout)."""
        model = 1
        if os.environ.get("PENROZ_SERVE_MESH", "0") == "1":
            try:
                model = max(1, int(os.environ.get(
                    "PENROZ_SERVE_MESH_MODEL", "1")))
            except ValueError:
                model = 1
        try:
            platform = (self.device.platform if self.device is not None
                        else None)
            devices = (jax.local_devices(backend=platform) if platform
                       else jax.local_devices())
        except RuntimeError:
            return kv, 1
        meshes = mesh_lib.serve_stage_meshes(pipe.stages, model=model,
                                             devices=devices,
                                             replica=replica)
        distinct = {d for m in meshes for d in np.asarray(m.devices).flat}
        if len(distinct) <= 1:
            pipe.meshes = None
            return kv, 1  # degenerate single-device group: no-op layout
        pipe.meshes = meshes
        log.info("Serving pipeline group: %d stages × %d-wide TP over "
                 "%d devices", pipe.stages, model, len(distinct))
        new_params = dict(self.params)
        new_buffers = dict(self.buffers)
        for s, mesh in enumerate(meshes):
            new_params.update(sharding_lib.shard_params(
                {k: v for k, v in self.params.items()
                 if self._stage_owns(pipe, s, k)}, mesh))
            new_buffers.update({
                k: sharding_lib.place(v, mesh_lib.replicated(mesh))
                for k, v in self.buffers.items()
                if self._stage_owns(pipe, s, k)})
        self.params, self.buffers = new_params, new_buffers
        if isinstance(kv, KV.PagedKVState):
            kv = sharding_lib.paged_kv_stage_shard(
                kv, meshes, pipe.kv_bounds, self.arch.kv_specs)
        return kv, len(distinct)

    @staticmethod
    def _stage_owns(pipe, s: int, key: str) -> bool:
        """Whether flat param/buffer ``key`` belongs to stage ``s``
        (non-``layers.`` keys ride with stage 0 — prologue state)."""
        if not key.startswith("layers."):
            return s == 0
        lo, hi = pipe.bounds[s]
        try:
            i = int(key[len("layers."):].split(".", 1)[0])
        except ValueError:
            return s == 0
        return lo <= i < hi

    def _kv_specs(self, batch: int = 1, max_len: int = 0):
        return self.arch.kv_specs

    def _generate_iter(self, context: list[int], block_size: int,
                       max_new_tokens: int, temperature: float,
                       top_k: Optional[int], metrics: Optional[KV.KVCache],
                       ramp: bool = False):
        """Yield new tokens one at a time, appending each to ``context``.

        Chunked, pipelined decode: one (re)prefill dispatch, then up to
        ``PENROZ_DECODE_CHUNK`` fused decode+sample steps per dispatch.  The
        next chunk is dispatched *before* the previous chunk's tokens are
        transferred to the host — the last sampled token stays on-device as
        the next chunk's input, so host-side conversion/yielding overlaps
        the device compute (a chunk dispatched past a ``stop_token`` is
        simply abandoned).  When the cache fills, the context is cropped
        and re-prefilled (reference overflow path:
        neural_net_model.py:375-389); the re-prefill needs the full host
        context, so the pipeline drains at that boundary.

        Chunk sizes are powers of two (bounded set of compiled programs).
        A tail shorter than its pow-2 ceiling dispatches the *ceiling* and
        discards the overshoot — a few wasted decode steps are far cheaper
        than the extra dispatch round-trips the descending pow-2
        decomposition would pay (e.g. 95 tail tokens = one 128-chunk, not
        64+16+8+4+2+1).  ``ramp=True`` (streaming) starts at 8 and doubles
        per dispatch so early tokens flow without waiting on a full chunk.
        """
        greedy, temp, call_rng = self._sampling_setup(temperature)
        chunk_budget = _chunk_budget()
        ramp_budget = 8 if ramp else chunk_budget
        decode = self.arch.decode_fn()
        # Cache layout (contiguous / paged / int8) is env-configured; the
        # contiguous decode kernel streams K/V tiles through its grid, so
        # long contexts need no auto-paging heuristic.
        kv = KV.create_kv_state(self.arch.kv_specs, 1, block_size,
                                self._kv_dtype(),
                                ssm_specs=self.arch.ssm_specs)
        kv = self._enter_decode_mesh(kv)
        cache_len = 0
        produced = 0    # tokens yielded to the caller
        dispatched = 0  # tokens sampled on-device (may run one chunk ahead)
        dispatch = 0
        last_dev = None  # (B, n) device tokens of the newest chunk
        pending = None   # (device tokens, count, dispatch time) to flush

        def flush(entry):
            nonlocal produced
            arr, count, dispatch_ms, logical, stored, state = entry
            t_wait = time.monotonic()
            toks = [int(t) for t in np.asarray(arr)[0][:count]]
            if metrics is not None:
                # dispatch (trace/enqueue) time of THIS chunk + the blocking
                # wait for its results; bytes captured at enqueue so a
                # pipelined successor's growth isn't charged to this chunk.
                wait_ms = (time.monotonic() - t_wait) * 1000
                metrics.record_step(count, logical, stored,
                                    dispatch_ms + wait_ms)
                metrics.final_state = state
            for tok in toks:
                context.append(tok)
                produced += 1
                yield tok
                if produced >= max_new_tokens:
                    return

        while produced < max_new_tokens:
            new_pending = None
            if dispatched < max_new_tokens:
                at_boundary = cache_len == 0 or cache_len >= block_size
                if at_boundary and pending is not None:
                    # Re-prefill reads context from the host: drain first.
                    yield from flush(pending)
                    pending = None
                    if produced >= max_new_tokens:
                        break
                    at_boundary = cache_len == 0 or cache_len >= block_size
                t0 = time.monotonic()
                rng = jax.random.fold_in(call_rng, dispatch)
                if at_boundary:
                    with tracing.span("penroz/prefill"):
                        kv = kv.reset()
                        feed = context[-block_size:]
                        x = jnp.asarray(np.asarray(feed, np.int64)[None, :],
                                        jnp.int32)
                        tok_arr, kv = decode(self.params, self.buffers, kv,
                                             x, rng, temp, greedy=greedy,
                                             top_k=top_k,
                                             platform=self._placement)
                        cache_len = len(feed)
                        new_pending = (tok_arr, 1,
                                       (time.monotonic() - t0) * 1000,
                                       kv.logical_bytes(), kv.memory_bytes(),
                                       kv)
                        last_dev = tok_arr
                        dispatched += 1
                else:
                    with tracing.span("penroz/decode_chunk"):
                        room = block_size - cache_len
                        remaining = max_new_tokens - dispatched
                        chunk = _decode_chunk_size(
                            remaining, min(chunk_budget, ramp_budget, room))
                        count = min(chunk, remaining)
                        toks_arr, kv = self.arch.decode_chunk(
                            self.params, self.buffers, kv,
                            last_dev[:, -1:], rng, temp, chunk=chunk,
                            greedy=greedy, top_k=top_k,
                            platform=self._placement)
                        cache_len += chunk
                        new_pending = (toks_arr, count,
                                       (time.monotonic() - t0) * 1000,
                                       kv.logical_bytes(), kv.memory_bytes(),
                                       kv)
                        last_dev = toks_arr
                        dispatched += count
                        ramp_budget = min(ramp_budget * 2, chunk_budget)
                dispatch += 1
            # Host conversion of the previous chunk overlaps the dispatch
            # above, which is still executing on-device.
            if pending is not None:
                yield from flush(pending)
            pending = new_pending
        if pending is not None and produced < max_new_tokens:
            yield from flush(pending)

    def generate_tokens_batched(self, inputs, block_size, max_new_tokens,
                                temperature=1.0, top_k=None,
                                stop_token=None) -> list[list[int]]:
        """RAGGED batched generation — N prompts of different lengths share
        one forward per step (beyond the reference, whose generate path is
        single-sequence: neural_net_model.py:457-479).

        Right-padded batched prefill (each row samples at its own last
        prompt position), then per-sequence cache lengths drive ragged
        decode: every row's K/V append, RoPE/position offset, and
        attention mask use that row's own length (ops/kv_cache.py
        ``with_lengths``, the ragged kernels/oracle).  Greedy outputs are
        bit-identical to N separate ``generate_tokens`` calls (tested).

        Contract: ``max(prompt) + max_new_tokens <= block_size`` — the
        batched path has no overflow crop/re-prefill.  Honors the same
        paged/int8 env flags as the single-sequence path (every cache
        variant supports ragged per-sequence lengths).
        """
        prompts = [[int(t) for t in (row if isinstance(row, (list, tuple))
                                     else [row])] for row in inputs]
        validate_batch_generation(prompts, block_size, max_new_tokens)
        B = len(prompts)
        lens = [len(p) for p in prompts]
        max_p = max(lens)
        greedy, temp, call_rng = self._sampling_setup(temperature)
        # Same compute dtype as the single-sequence decode path (its
        # decode_fn default) — anything else would break the documented
        # batched ≡ single greedy parity on near-tied logits.
        compute_dtype = None
        arch = self.arch

        key = ("bprefill", bool(greedy), top_k, str(compute_dtype),
               self._placement)
        prefill = arch._jit_cache.get(key)
        if prefill is None:
            def prefill_fn(p, bufs, kv0, toks, lengths, r, tmp):
                acts, _, _, kv1 = arch.forward(
                    p, bufs, toks, None, kv=kv0, skip_softmax=True,
                    compute_dtype=compute_dtype, platform=self._placement)
                logits = acts[-1]
                last = jnp.take_along_axis(
                    logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
                tok = arch._sample(last, r, tmp, greedy=greedy, top_k=top_k)
                return tok, kv1.with_lengths(lengths)
            prefill = arch._jit_cache[key] = jax.jit(
                prefill_fn, donate_argnums=(2,))

        outs = [list(p) for p in prompts]
        if max_new_tokens <= 0:
            return outs
        padded = np.zeros((B, max_p), np.int32)
        for i, p in enumerate(prompts):
            padded[i, :len(p)] = p
        # Same env-flag factory as the single-sequence path: paged / int8
        # pools do ragged batches too (per-sequence lengths thread through
        # the allocator, appends, and the ragged kernels).
        kv = KV.create_kv_state(arch.kv_specs, B, block_size,
                                self._kv_dtype(),
                                ssm_specs=arch.ssm_specs)
        kv = self._enter_decode_mesh(kv, batch=B)
        lengths = jnp.asarray(lens, jnp.int32)
        done = [False] * B

        def absorb(arr):
            for i, t in enumerate(arr):
                if not done[i]:
                    outs[i].append(int(t))
                    done[i] = (stop_token is not None
                               and int(t) == stop_token)

        with decode_priority():
            prev, kv = prefill(self.params, self.buffers, kv,
                               jnp.asarray(padded), lengths,
                               jax.random.fold_in(call_rng, 0), temp)
            absorb(np.asarray(prev))
            # Fused chunked decode (same scan programs as _generate_iter's
            # decode_chunk, same pow-2-ceiling tails): up to
            # PENROZ_DECODE_CHUNK steps per dispatch instead of one.  The
            # overshoot bound uses the longest prompt, which every row's
            # capacity satisfies (validated above); tokens scanned past an
            # all-rows stop are abandoned.  With a stop_token, ramp from 8
            # doubling per dispatch (as the streaming path does) so an
            # early stop wastes at most the current ramp chunk, not a full
            # budget of fused steps.
            chunk_budget = _chunk_budget()
            ramp_budget = 8 if stop_token is not None else chunk_budget
            last = prev[:, None]
            dispatched = 1
            while dispatched < max_new_tokens and not all(done):
                remaining = max_new_tokens - dispatched
                room = block_size - max_p - dispatched
                chunk = _decode_chunk_size(
                    remaining, min(chunk_budget, ramp_budget, room))
                count = min(chunk, remaining)
                ramp_budget = min(ramp_budget * 2, chunk_budget)
                toks, kv = arch.decode_chunk(
                    self.params, self.buffers, kv, last,
                    jax.random.fold_in(call_rng, dispatched), temp,
                    chunk=chunk, greedy=greedy, top_k=top_k,
                    platform=self._placement)
                arr = np.asarray(toks)[:, :count]
                for col in range(count):
                    absorb(arr[:, col])
                    if all(done):
                        break
                last = toks[:, -1:]
                dispatched += count
        return outs

    # -- step-wise decode API (continuous-batching scheduler) ---------------

    @staticmethod
    def _norm_temperature(temperature):
        """(greedy, temp scalar) with the same None/0.0 → greedy rule as
        ``_sampling_setup`` (no rng split — the scheduler owns its rng)."""
        greedy = temperature is None or float(temperature) == 0.0
        temp = jnp.asarray(float(temperature) if temperature else 1.0,
                           jnp.float32)
        return greedy, temp

    def decode_prefill_single(self, prompt: list[int], block_size: int,
                              rng, temperature=1.0, top_k=None):
        """Prefill one prompt into a fresh batch-1 KV state and sample its
        first token — the exact program the single-sequence generate loop
        dispatches (``_generate_iter``'s prefill), so the first token of a
        scheduler-admitted request is identical to the standalone path.
        Returns ``(first_token:int, kv_single, fed_len:int)``."""
        greedy, temp = self._norm_temperature(temperature)
        decode = self.arch.decode_fn()
        kv = KV.create_kv_state(self.arch.kv_specs, 1, block_size,
                                self._kv_dtype(),
                                ssm_specs=self.arch.ssm_specs)
        feed = prompt[-block_size:]
        x = jnp.asarray(np.asarray(feed, np.int64)[None, :], jnp.int32)
        tok_arr, kv = decode(self.params, self.buffers, kv, x, rng, temp,
                             greedy=greedy, top_k=top_k,
                             platform=self._placement)
        return int(np.asarray(tok_arr)[0, 0]), kv, len(feed)

    def decode_prefill_chunk(self, kv_batch, row: int, tokens, row_len: int,
                             rng, temperature=1.0, top_k=None, lora=None,
                             adapter_slot: int = 0):
        """Feed one prompt chunk for row ``row`` directly into the multi-row
        decode state — the chunked-prefill dispatch the scheduler interleaves
        between shared decode steps so a long prompt never stalls the batch
        for more than one chunk.

        ``tokens`` (T,) extends the row's KV from valid length ``row_len``
        (positions ``row_len + [0, T)``): the chunk attends the row's
        existing cache (including any radix-aliased prefix pages on the
        paged variants) through the same ``cached_attention`` program family
        as one-shot prefill, and its K/V appends land in the row's own
        pages/buffers via ``KVState.row_view``/``merge_row``.  Returns
        ``(sampled_token:int, kv_batch')`` — the token is the greedy/sampled
        continuation at the chunk's last position, i.e. the request's first
        generated token when this was the final chunk (identical to the
        one-shot path: same logits position, same program family).  Jits per
        (T, cache type, sampling); keep chunk sizes power-of-two-bucketed so
        the program set stays bounded.  Donates ``kv_batch`` — always thread
        the returned state.
        """
        greedy, temp = self._norm_temperature(temperature)
        arch = self.arch
        T = len(tokens)
        key = ("prefill_chunk", T, type(kv_batch).__name__, bool(greedy),
               top_k, self._placement)
        fn = arch._jit_cache.get(key)
        if fn is None:
            platform = self._placement

            def chunk_step(p, b, kvb, toks, r_idx, r_len, r, tmp, lo, ai):
                view = kvb.row_view(r_idx, r_len)
                tok, view2 = arch._decode_step(p, b, view, toks, r, tmp,
                                               greedy=greedy, top_k=top_k,
                                               compute_dtype=None,
                                               platform=platform,
                                               lora=lo, lora_idx=ai)
                return tok[0, 0], kvb.merge_row(r_idx, view2)

            fn = arch._jit_cache[key] = jax.jit(chunk_step,
                                                donate_argnums=(2,))
        x = jnp.asarray(np.asarray(tokens, np.int64)[None, :], jnp.int32)
        aidx = (jnp.asarray([adapter_slot], jnp.int32)
                if lora is not None else None)
        with tracing.span("penroz/decode_prefill_chunk"):
            tok, kv_out = fn(self.params, self.buffers, kv_batch, x,
                             jnp.asarray(row, jnp.int32),
                             jnp.asarray(row_len, jnp.int32), rng, temp,
                             lora, aidx)
        return int(np.asarray(tok)), kv_out

    def decode_verify_row(self, kv_batch, row: int, tokens, row_len: int,
                          rng, temperature=1.0, top_k=None, lora=None,
                          adapter_slot: int = 0):
        """Speculative-decoding verify step for one row: one forward over
        the row's T candidate tokens (``tokens[0]`` is the last sampled
        token, the rest a drafter's proposals), sampling at EVERY position.

        Same program family and write path as :meth:`decode_prefill_chunk`
        (``row_view``/``merge_row`` over all four cache variants, appends
        at ``row_len + [0, T)``) — the only difference is that all T
        sampled tokens come back instead of the last one, so the scheduler
        can accept the longest greedy-matching prefix and roll the row's
        KV back past the rejected positions (``KVState.rollback_row``;
        lengths here stay host-authoritative exactly as in the chunk
        path).  Returns ``(list[int] of T sampled tokens, kv_batch')``.
        Jits per (T, cache type, sampling) — keep draft lengths
        power-of-two-bucketed so the program set stays bounded.  Donates
        ``kv_batch`` — always thread the returned state.
        """
        greedy, temp = self._norm_temperature(temperature)
        arch = self.arch
        T = len(tokens)
        key = ("verify_row", T, type(kv_batch).__name__, bool(greedy),
               top_k, self._placement)
        fn = arch._jit_cache.get(key)
        if fn is None:
            platform = self._placement

            def verify_step(p, b, kvb, toks, r_idx, r_len, r, tmp, lo, ai):
                view = kvb.row_view(r_idx, r_len)
                acts, _, _, view2 = arch.forward(
                    p, b, toks, None, kv=view, pos_offset=view.length,
                    skip_softmax=True, compute_dtype=None,
                    platform=platform, lora=lo, lora_idx=ai)
                logits = acts[-1]          # (1, T, V)
                out = arch._sample(logits[0], r, tmp, greedy=greedy,
                                   top_k=top_k)          # (T,)
                return out, kvb.merge_row(r_idx, view2)

            fn = arch._jit_cache[key] = jax.jit(verify_step,
                                                donate_argnums=(2,))
        x = jnp.asarray(np.asarray(tokens, np.int64)[None, :], jnp.int32)
        aidx = (jnp.asarray([adapter_slot], jnp.int32)
                if lora is not None else None)
        with tracing.span("penroz/decode_verify_row"):
            out, kv_out = fn(self.params, self.buffers, kv_batch, x,
                             jnp.asarray(row, jnp.int32),
                             jnp.asarray(row_len, jnp.int32), rng, temp,
                             lora, aidx)
        return [int(t) for t in np.asarray(out)], kv_out

    def decode_insert_row(self, kv_batch, row: int, kv_single):
        """Jitted per-row admission: drop a prefilled batch-1 state into
        row ``row`` of the persistent multi-row decode cache
        (``ops.kv_cache.KVState.insert_row``).  One compiled program covers
        every slot — ``row`` is traced.  Donates ``kv_batch``."""
        key = ("insert_row", type(kv_batch).__name__, self._placement)
        fn = self.arch._jit_cache.get(key)
        if fn is None:
            def ins(kvb, kvs, r):
                return kvb.insert_row(r, kvs)
            fn = self.arch._jit_cache[key] = jax.jit(ins, donate_argnums=(0,))
        return fn(kv_batch, kv_single, jnp.asarray(row, jnp.int32))

    def decode_step_batched(self, kv, last_tokens, lengths, rng,
                            temperature=1.0, top_k=None, lora=None,
                            row_adapter=None, dispatch=None):
        """One shared decode+sample step across every row of a persistent
        multi-row KV state — the continuous-batching hot loop: K in-flight
        requests cost one batch-K forward per token instead of K batch-1
        forwards.

        ``lengths`` (B,) is the host's authoritative per-row valid length
        (0 parks a free slot: its write lands at position 0 of its own row
        and is never attended); it is installed via ``with_lengths`` inside
        the jitted step, so recycled/idle rows never drift on-device.
        With ``dispatch`` set, ``rng`` is the caller's BASE key and the
        per-step key advance ``fold_in(rng, dispatch)`` happens inside the
        jitted program — the caller passes the same base key every step
        plus an integer, instead of launching a host-side fold dispatch
        per token (``fold_in`` is bit-identical either side of the jit
        boundary, so seeded non-greedy output is unchanged — tested).
        Returns ``((B,) int32 next tokens, advanced kv)``; greedy outputs
        per row are identical to the single-sequence path (same ragged
        decode program as ``generate_tokens_batched``).  Donates ``kv`` —
        always thread the returned state.
        """
        greedy, temp = self._norm_temperature(temperature)
        arch = self.arch
        fold = dispatch is not None
        key = ("sched_step", bool(greedy), top_k, self._placement, fold)
        fn = arch._jit_cache.get(key)
        if fn is None:
            platform = self._placement

            def step(p, b, kv0, tok, lens, r, d, tmp, lo, ai):
                if fold:
                    r = jax.random.fold_in(r, d)
                kv1 = kv0.with_lengths(lens)
                t, kv2 = arch._decode_step(p, b, kv1, tok, r, tmp,
                                           greedy=greedy, top_k=top_k,
                                           compute_dtype=None,
                                           platform=platform,
                                           lora=lo, lora_idx=ai)
                return t[:, 0], kv2

            fn = arch._jit_cache[key] = jax.jit(step, donate_argnums=(2,))
        aidx = (jnp.asarray(row_adapter, jnp.int32)
                if lora is not None else None)
        with tracing.span("penroz/decode_step_batched"):
            return fn(self.params, self.buffers, kv,
                      jnp.asarray(last_tokens, jnp.int32),
                      jnp.asarray(lengths, jnp.int32), rng,
                      jnp.asarray(dispatch if fold else 0, jnp.int32),
                      temp, lora, aidx)

    def decode_superstep(self, kv, last_tokens, lengths, active,
                         stop_tokens, remaining, rng, dispatch, n,
                         temperature=1.0, top_k=None, lora=None,
                         row_adapter=None):
        """Run up to ``n`` shared decode+sample steps in ONE jitted
        dispatch — a ``lax.scan`` over the exact per-step program of
        :meth:`decode_step_batched`, so the host dispatch floor (sync
        lengths, check stop tokens, launch again) is paid once per ``n``
        tokens instead of once per token.

        The scan carry is ``(kv, last_tok, lengths, active, emitted)``:

        - ``kv`` threads through the scan donated-in, so the cache
          advances on device without host copies on all four variants
          (fp/int8 × contiguous/paged — the paged variants walk their
          static block-table partition with trace-static shapes exactly
          as in the single-step program);
        - ``lengths`` (B,) stays carry-authoritative and is re-installed
          via ``with_lengths`` each iteration, advancing by 1 only for
          ``active`` rows — parked/finished rows keep writing their
          compute-but-discard K/V at the same parked position, exactly
          like padded rows in the single-step path;
        - ``active`` (B, bool) is the on-device stop detector: a row
          leaves the mask when it samples its stop token, exhausts its
          ``remaining`` token budget, or fills the cache
          (``length == max_len``).  Finished rows keep computing and
          discard (``where``) — the program stays trace-static;
        - the sampling key for scan step ``i`` is
          ``fold_in(rng, dispatch + i)`` — the identical key sequence
          the host-folded single-step path would produce over the same
          ``n`` dispatch ordinals, so seeded non-greedy output is
          unchanged by fusing (tested; greedy ignores the key entirely).

        ``stop_tokens`` (B,) carries -1 for rows with no stop token;
        ``remaining`` (B,) is the per-row token budget left.  Returns
        ``(toks (n, B) int32 with -1 at masked slots, emitted (n, B)
        bool, final_lengths (B,), kv')`` — ONE host sync for the whole
        block; the scheduler replays ``toks[s, i]`` where ``emitted[s,
        i]`` through its normal per-token retirement path at the
        superstep boundary.  Jits per (n, sampling, cache type); keep
        ``n`` power-of-two-bucketed so the program set stays bounded.
        Donates ``kv`` — always thread the returned state.
        """
        greedy, temp = self._norm_temperature(temperature)
        arch = self.arch
        key = ("superstep", int(n), bool(greedy), top_k, self._placement)
        fn = arch._jit_cache.get(key)
        if fn is None:
            platform = self._placement

            def run(p, b, kv0, tok0, len0, act0, stopt, rem, r, d0, tmp,
                    lo, ai):
                max_len = kv0.max_len  # static

                def step(carry, i):
                    kvc, tok, lens, act, done = carry
                    kv1 = kvc.with_lengths(lens)
                    r_i = jax.random.fold_in(r, d0 + i)
                    t, kv2 = arch._decode_step(p, b, kv1, tok, r_i, tmp,
                                               greedy=greedy, top_k=top_k,
                                               compute_dtype=None,
                                               platform=platform,
                                               lora=lo, lora_idx=ai)
                    t = t[:, 0]
                    new_tok = jnp.where(act, t, tok[:, 0])[:, None]
                    new_lens = lens + act.astype(lens.dtype)
                    new_done = done + act.astype(jnp.int32)
                    still = (act & (t != stopt) & (new_done < rem)
                             & (new_lens < max_len))
                    out = (jnp.where(act, t, -1), act)
                    return (kv2, new_tok, new_lens, still, new_done), out

                init = (kv0, tok0, len0, act0,
                        jnp.zeros_like(len0))
                (kvf, _, lensf, _, _), (toks, emitted) = jax.lax.scan(
                    step, init, jnp.arange(n, dtype=jnp.int32))
                return toks, emitted, lensf, kvf

            fn = arch._jit_cache[key] = jax.jit(run, donate_argnums=(2,))
        aidx = (jnp.asarray(row_adapter, jnp.int32)
                if lora is not None else None)
        with tracing.span("penroz/decode_superstep"):
            return fn(self.params, self.buffers, kv,
                      jnp.asarray(last_tokens, jnp.int32),
                      jnp.asarray(lengths, jnp.int32),
                      jnp.asarray(active, bool),
                      jnp.asarray(stop_tokens, jnp.int32),
                      jnp.asarray(remaining, jnp.int32), rng,
                      jnp.asarray(dispatch, jnp.int32), temp, lora, aidx)

    def decode_mixed_step(self, kv, descs, tok_lit, tok_src, positions,
                          sample_slot, last_tokens, rng, dispatch,
                          temperature=1.0, top_k=None, lora=None,
                          lora_slots=None, row_ids=None):
        """Run ``n`` unified RAGGED steps in one dispatch — the single
        program that subsumes :meth:`decode_prefill_chunk`,
        :meth:`decode_step_batched` and :meth:`decode_verify_row` for
        paged caches: every step is one packed mixed batch where prefill
        chunks, decode steps and spec-verify spans share one kernel
        dispatch (ops/pallas/ragged_paged_attention.py), appends scatter
        straight through the block table (no ``row_view``
        materialization), and sampling happens at every packed position.

        The host plans the whole block up front (it knows each row's
        prompt, so a row can finish its prefill at step s and decode from
        step s+1 *inside the same dispatch* — the ``tok_src`` indirection
        feeds the carry's freshly sampled token forward), then replays
        emissions from the returned ``(n, Tp)`` sample array:

        - ``descs`` (n, NB, 4) int32 per-step descriptor arrays
          (ops/kv_cache.py::build_descriptors; NB shape-bucketed —
          utils/bucketing.py::bucket_count — so the program set stays
          bounded);
        - ``tok_lit``/``tok_src`` (n, Tp): packed input tokens — slot p
          feeds ``last_tokens[tok_src]`` when ``tok_src ≥ 0`` (decode
          continuation) else the literal (prompt/draft tokens);
        - ``positions`` (n, Tp) int32 absolute position per packed slot
          (per-token RoPE);
        - ``sample_slot`` (n, B): the packed slot whose sample becomes
          row b's carry ``last_token`` after that step (-1 keeps it —
          parked rows, non-final prefill chunks);
        - ``lora_slots`` (n, Tp) per-TOKEN adapter slots when ``lora``
          is set (the per-row gather rides the same dispatch).

        The GREEDY sampling key for step ``i`` is ``fold_in(rng,
        dispatch+i)``, the same sequence the phased path folds over its
        dispatch ordinals (unused by argmax; kept for program identity).
        Non-greedy sampling uses POSITIONAL keys —
        :meth:`CompiledArch._sample_packed` over ``row_ids`` (n, Tp, row
        index per packed slot, -1 padding) — so a (row, position) draw is
        invariant to packing, superstep, chunk splits and pipeline
        micro-blocking; spec-on/off and pipeline parity at temperature>0
        ride on this.  Returns ``(sampled (n, Tp) int32, kv')``; the caller
        replays per-row emissions (stop tokens, verify acceptance,
        rollbacks) host-side — host lengths stay authoritative exactly
        as on the phased path.  Jits per (n, NB, Tp, sampling, cache
        type).  Donates ``kv`` — always thread the returned state.
        """
        greedy, temp = self._norm_temperature(temperature)
        arch = self.arch
        descs = np.asarray(descs, np.int32)
        n, NB = descs.shape[0], descs.shape[1]
        tok_lit = np.asarray(tok_lit, np.int32)
        Tp = tok_lit.shape[1]
        if Tp % NB != 0:
            raise ValueError(f"packed length {Tp} must be a multiple of "
                             f"the descriptor count {NB}")
        block_q = Tp // NB
        key = ("mixed_step", n, NB, Tp, type(kv).__name__, bool(greedy),
               top_k, self._placement, lora is not None)
        fn = arch._jit_cache.get(key)
        if fn is None:
            platform = self._placement

            def run(p, b, kv0, dsc_s, tlit_s, tsrc_s, pos_s, sslot_s,
                    li_s, rid_s, last0, r, d0, tmp, lo):
                def step(carry, x):
                    kvc, last = carry
                    dsc, tlit, tsrc, pos, sslot, li, rid, i = x
                    toks = jnp.where(tsrc >= 0,
                                     last[jnp.clip(tsrc, 0)], tlit)
                    rows = kvc.packed_rows(dsc, block_q)
                    r_i = jax.random.fold_in(r, d0 + i)
                    acts, _, _, kv2 = arch.forward(
                        p, b, toks[None, :], None, kv=kvc,
                        pos_offset=pos[None, :], skip_softmax=True,
                        compute_dtype=None, platform=platform, lora=lo,
                        lora_idx=(li[None, :] if lo is not None else None),
                        ragged_descs=dsc, ragged_rows=rows)
                    logits = acts[-1][0]                       # (Tp, V)
                    if greedy:
                        out = arch._sample(logits, r_i, tmp, greedy=True,
                                           top_k=top_k)        # (Tp,)
                    else:
                        out = arch._sample_packed(logits, r, rid, pos,
                                                  tmp, top_k)  # (Tp,)
                    new_last = jnp.where(sslot >= 0,
                                         out[jnp.clip(sslot, 0)], last)
                    return (kv2, new_last), out

                xs = (dsc_s, tlit_s, tsrc_s, pos_s, sslot_s, li_s, rid_s,
                      jnp.arange(n, dtype=jnp.int32))
                (kvf, _), sampled = jax.lax.scan(step, (kv0, last0), xs)
                return sampled, kvf

            fn = arch._jit_cache[key] = jax.jit(run, donate_argnums=(2,))
        li = (np.asarray(lora_slots, np.int32) if lora_slots is not None
              else np.zeros((n, Tp), np.int32))
        rid = (np.asarray(row_ids, np.int32) if row_ids is not None
               else np.full((n, Tp), -1, np.int32))
        with tracing.span("penroz/decode_mixed_step"):
            return fn(self.params, self.buffers, kv,
                      jnp.asarray(descs), jnp.asarray(tok_lit),
                      jnp.asarray(tok_src, jnp.int32).reshape(n, Tp),
                      jnp.asarray(positions, jnp.int32).reshape(n, Tp),
                      jnp.asarray(sample_slot, jnp.int32),
                      jnp.asarray(li), jnp.asarray(rid.reshape(n, Tp)),
                      jnp.asarray(last_tokens, jnp.int32),
                      rng, jnp.asarray(dispatch, jnp.int32), temp, lora)

    def serve_pipeline(self, stages: int) -> "ServePipeline":
        """Build (and validate) the MPMD serving stage partition for this
        model — raises ``ValueError`` when the DSL has fewer repeated
        blocks than ``stages`` or a stage would own no attention layer."""
        return ServePipeline(self.arch, stages)

    def decode_pipe_stage(self, pipe: "ServePipeline", s: int, kv_stage,
                          x, descs, positions, row_ids, rng,
                          temperature=1.0, top_k=None):
        """Run ONE pipeline stage of one unified ragged step over one
        micro-block — the MPMD counterpart of a single
        :meth:`decode_mixed_step` scan iteration, split at stage
        boundaries.  Stage 0 consumes packed tokens ``x`` (1, Tp) int32
        (the host resolves the ``tok_src`` indirection — it already owns
        ``last_tokens`` between micro-blocks); later stages consume the
        previous stage's hidden-state hand-off (1, Tp, D).  Every stage
        appends into its own KV slice via ``kv_stage``
        (ops/kv_cache.py::stage_kv_view) — stage archs index attention
        layers 0.. locally, matching the sliced pools.  The LAST stage
        samples: greedy argmax (bit-identical to the fused program — the
        module stack is split only at module boundaries, so the logits
        are the same floats) or :meth:`CompiledArch._sample_packed`
        positional draws (identical to the unpiped non-greedy stream by
        construction).  Returns ``(hidden|sampled, kv_stage')``.

        Jits per (stage, NB, Tp, cache type, sampling); cached in the
        STAGE arch's program cache so ``jit_program_counts`` attributes
        them per stage.  Deliberately does NOT donate ``kv_stage``: its
        counters/table/lengths buffers are shared with every other
        stage's view of the same cache (and with the full state the
        scheduler threads), so donation would invalidate siblings —
        correctness over the copy-elision, documented perf gap."""
        greedy, temp = self._norm_temperature(temperature)
        arch_s = pipe.archs[s]
        descs = np.asarray(descs, np.int32)
        NB = descs.shape[0]
        positions = np.asarray(positions, np.int32)
        Tp = positions.shape[-1]
        if Tp % NB != 0:
            raise ValueError(f"packed length {Tp} must be a multiple of "
                             f"the descriptor count {NB}")
        block_q = Tp // NB
        last_stage = s == pipe.stages - 1
        # each stage's program is partitioned over that stage's own mesh
        platform = self._platform
        if pipe.meshes is not None and pipe.meshes[s].size > 1:
            platform = attn_ops.Placement(platform, pipe.meshes[s])
        key = ("pipe_stage", s, pipe.stages, NB, Tp,
               type(kv_stage).__name__, bool(greedy), top_k, platform)
        fn = arch_s._jit_cache.get(key)
        if fn is None:

            def run(p, b, kv0, xx, dsc, pos, rid, r, tmp):
                rows = kv0.packed_rows(dsc, block_q)
                acts, _, _, kv2 = arch_s.forward(
                    p, b, xx, None, kv=kv0, pos_offset=pos[None, :],
                    skip_softmax=True, compute_dtype=None,
                    platform=platform, ragged_descs=dsc, ragged_rows=rows)
                h = acts[-1]
                if not last_stage:
                    return h, kv2
                logits = h[0]                                  # (Tp, V)
                if greedy:
                    out = arch_s._sample(logits, r, tmp, greedy=True,
                                         top_k=top_k)
                else:
                    out = arch_s._sample_packed(logits, r, rid, pos,
                                                tmp, top_k)
                return out, kv2

            fn = arch_s._jit_cache[key] = jax.jit(run)
        params = pipe.stage_params(self.params, s)
        buffers = pipe.stage_buffers(self.buffers, s)
        if s == 0:
            x = jnp.asarray(np.asarray(x, np.int32).reshape(1, Tp))
        if pipe.meshes is not None:
            # MPMD placement is live: pull the shared KV metadata and the
            # previous stage's activation hand-off onto THIS stage's mesh
            # (device-to-device) so the stage jit sees one device group.
            repl = mesh_lib.replicated(pipe.meshes[s])
            kv_stage = KV.restage_shared(kv_stage, repl)
            if s > 0 and isinstance(x, jax.Array):
                x = jax.device_put(x, repl)
        with tracing.span("penroz/decode_pipe_stage"):
            return fn(params, buffers, kv_stage, x, jnp.asarray(descs),
                      jnp.asarray(positions.reshape(Tp)),
                      jnp.asarray(np.asarray(row_ids,
                                             np.int32).reshape(Tp)),
                      rng, temp)

    def _sampling_setup(self, temperature):
        """Shared generation preamble: (greedy, temp scalar, call rng).
        None/0.0 temperature means greedy; falsy maps the scalar to 1.0
        (reference sampling knobs: neural_net_model.py:393-405)."""
        greedy = temperature is None or float(temperature) == 0.0
        temp = jnp.asarray(float(temperature) if temperature else 1.0,
                           jnp.float32)
        self._sample_rng, call_rng = jax.random.split(self._sample_rng)
        return greedy, temp, call_rng

    @staticmethod
    def _prompt_tokens(input) -> list[int]:
        row = input[0] if input and isinstance(input[0], (list, tuple)) \
            else input
        return [int(t) for t in row]

    def generate_tokens(self, input, block_size, max_new_tokens,
                        temperature=1.0, top_k=None, stop_token=None):
        """Autoregressive generation; returns prompt + generated ids
        (reference: neural_net_model.py:457-479)."""
        context = self._prompt_tokens(input)
        metrics = KV.create_kv_cache(len(self.arch.attn_layers))
        try:
            with decode_priority():
                for tok in self._generate_iter(context, block_size,
                                               max_new_tokens, temperature,
                                               top_k, metrics):
                    if stop_token is not None and tok == stop_token:
                        break
        finally:
            metrics.log_metrics()
        return context

    def generate_tokens_stream(self, input, block_size, max_new_tokens,
                               temperature=1.0, top_k=None, stop_token=None):
        """Streaming variant yielding each new token (reference:
        neural_net_model.py:481-514)."""
        context = self._prompt_tokens(input)
        metrics = KV.create_kv_cache(len(self.arch.attn_layers))
        it = self._generate_iter(context, block_size, max_new_tokens,
                                 temperature, top_k, metrics, ramp=True)
        try:
            while True:
                # Mark only the device-work advance, not the consumer's
                # wall time between yields — a slow stream reader must not
                # keep training parked at the priority window with an
                # idle chip.
                with decode_priority():
                    try:
                        tok = next(it)
                    except StopIteration:
                        break
                yield tok
                if stop_token is not None and tok == stop_token:
                    return
        finally:
            metrics.log_metrics()

    # -- persistence --------------------------------------------------------

    @staticmethod
    def _is_host_readable(v) -> bool:
        """Whether ``np.asarray(v)`` works on this host (plain / addressable
        / fully-replicated arrays — everything except cross-host shards)."""
        return (getattr(v, "is_fully_addressable", True)
                or getattr(v, "is_fully_replicated", False))

    def _checkpoint_items(self):
        """Flat name → array view of everything persisted (params, buffers,
        optimizer leaves) so sharding-aware save/load handles them
        uniformly.  Optimizer leaves get synthetic ``__opt__{i}`` names.
        An active pipeline-stacked training layout is converted back to the
        canonical flat layout here, so the checkpoint format (and
        :meth:`deserialize`) never sees stacked keys."""
        params, opt_state = self._canonical_state()
        items = dict(params)
        items.update({f"__buf__{k}": v for k, v in self.buffers.items()})
        items.update({f"__opt__{i}": leaf for i, leaf
                      in enumerate(jax.tree.leaves(opt_state))})
        return items

    def serialize(self, sync_flush: bool = False, tag=None):
        """Checkpoint to shm + durable dir (reference:
        neural_net_model.py:98-122).

        Cross-host-sharded arrays (TP/SP/EP over a multi-host mesh) cannot be
        materialized on one host; each process persists the shard pieces it
        owns (``replica_id == 0`` only, so the union covers each index range
        exactly once) into ``model_{id}.shard{rank}.ckpt``, and the master
        blob records their global shape/dtype for reassembly on load.
        ``tag`` (the epoch number during training — identical on every host)
        is stamped into the blob and every shard file so a load can reject a
        checkpoint whose pieces come from different training steps.

        An UNTAGGED call on a model with sharded params (a status update at
        train start, the error path, a serve-side save) is not coordinated
        across hosts, so it must not rewrite shard files — one host's write
        would permanently tear the last consistent checkpoint.  Such calls
        degrade to a master-only metadata update of the existing blob.
        The raw-layout check runs BEFORE the canonical conversion: with a
        pipeline-stacked layout still active, unstacking cross-host leaves
        is itself a collective, and an uncoordinated call must not launch
        one one-sided.

        One ``penroz/ckpt_save`` span (``periodic``: tagged, i.e. a
        checkpoint of a training step — the 10 s cadence and the job's
        last) with the d2h / encode / write / flush anatomy beneath it."""
        with tracing.span("penroz/ckpt_save", tag=tag,
                          periodic=tag is not None) as save_span:
            nbytes = self._serialize(sync_flush, tag)
            if nbytes is not None:
                save_span.set(bytes=nbytes)

    def _serialize(self, sync_flush: bool, tag) -> int | None:
        """:meth:`serialize` proper; the main blob's size in bytes where
        this process wrote one."""
        if tag is None:
            # Raw-layout check over params + buffers + optimizer leaves:
            # buffers are placed replicated at train start, but epoch
            # OUTPUTS (e.g. pipelined MoE router fractions from the aux
            # channel) carry whatever sharding GSPMD propagated, so they
            # must be checked, not assumed.
            raw_sharded = not all(
                self._is_host_readable(v) for v in (
                    list(self.params.values())
                    + list(self.buffers.values())
                    + jax.tree.leaves(self.opt_state)))
            if raw_sharded:
                if dist.master_proc():
                    self._serialize_meta_only(sync_flush)
                return
        items = self._checkpoint_items()
        sharded_meta: dict = {}
        shard_pieces: dict = {}
        for name, v in items.items():
            if not self._is_host_readable(v):
                sharded_meta[name] = {"shape": tuple(v.shape),
                                      "dtype": str(v.dtype)}
                shard_pieces[name] = [
                    (tuple((sl.start, sl.stop) for sl in shard.index),
                     np.asarray(shard.data))
                    for shard in v.addressable_shards
                    if shard.replica_id == 0]
        if shard_pieces:
            checkpoint.save_shard(
                self.model_id, dist.process_index(),
                {"tag": tag, "pieces": shard_pieces},
                sync_flush=sync_flush, world=dist.process_count())
        if not dist.master_proc():
            return
        # Host-readable materialization only after the master check — every
        # non-master host doing full D2H copies of replicated state just to
        # discard them would waste seconds per checkpoint at scale.
        with tracing.span("penroz/ckpt_d2h") as d2h_span:
            host_arrays = {name: np.asarray(v) for name, v in items.items()
                           if self._is_host_readable(v)}
            d2h_span.set(arrays=len(host_arrays),
                         bytes=sum(a.nbytes for a in host_arrays.values()))
        # Key/leaf sets come from the canonical layout (== items), not
        # self.params/opt_state, which may be pipeline-stacked mid-training.
        n_opt = sum(1 for name in items if name.startswith("__opt__"))
        params = {k: host_arrays[k] for k in items
                  if not k.startswith(("__buf__", "__opt__"))
                  and k in host_arrays}
        buffers = {k: host_arrays[f"__buf__{k}"] for k in self.buffers
                   if f"__buf__{k}" in host_arrays}
        opt_leaves = {i: host_arrays[f"__opt__{i}"] for i in range(n_opt)
                      if f"__opt__{i}" in host_arrays}
        data = {
            "layers": self.layers_dsl,
            "optimizer": self.optimizer_config,
            "params": params,
            "buffers": buffers,
            "opt_state_leaves": opt_leaves,
            "sharded": sharded_meta,
            "shard_tag": tag,
            "progress": self.progress,
            "avg_cost": self.avg_cost,
            "avg_cost_history": self.avg_cost_history,
            "stats": self.stats,
            "status": self.status,
        }
        return checkpoint.save(self.model_id, data, sync_flush=sync_flush)

    def _serialize_meta_only(self, sync_flush: bool = False):
        """Update progress/status in the existing blob without touching the
        weights or shard files — the safe write for uncoordinated saves on a
        sharded model (preserves the last consistent checkpoint).

        ``checkpoint.patch_meta`` rewrites only the header and streams the
        array payload through verbatim — no decode, no re-encode, no RAM
        spike on multi-GB checkpoints.  (``sync_flush`` is moot:
        patch_meta always writes both copies synchronously.)"""
        del sync_flush
        try:
            checkpoint.patch_meta(self.model_id, {
                "progress": self.progress,
                "avg_cost": self.avg_cost,
                "avg_cost_history": self.avg_cost_history,
                "stats": self.stats,
                "status": self.status,
            })
        except KeyError:
            log.warning("Meta-only checkpoint skipped: no existing blob "
                        "for %s", self.model_id)

    @staticmethod
    def _reassemble_sharded(model_id: str, sharded_meta: dict,
                            expected_tag=None) -> dict:
        """Rebuild full arrays from the per-host shard files (TP/SP/EP
        checkpoints).  Requires every host's shard file to be readable —
        true on shared filesystems and in tests; raises otherwise.  Shard
        files stamped with a different step tag than the blob are rejected
        (a crash between hosts' checkpoints would otherwise stitch weight
        pieces from different training steps)."""
        shards = []
        for i, payload in enumerate(checkpoint.load_shards(model_id)):
            if payload.get("tag") != expected_tag:
                raise RuntimeError(
                    f"Sharded checkpoint for {model_id} is torn: shard file "
                    f"#{i} is from step {payload.get('tag')!r} but the "
                    f"metadata blob is from step {expected_tag!r}")
            shards.append(payload["pieces"])
        out = {}
        for name, meta in sharded_meta.items():
            shape = tuple(meta["shape"])
            # checkpoint.np_dtype: plain np.dtype cannot parse "bfloat16"
            arr = np.zeros(shape, dtype=checkpoint.np_dtype(meta["dtype"]))
            covered = 0
            for shard_data in shards:
                for ranges, piece in shard_data.get(name, []):
                    idx = tuple(slice(a, b) for a, b in ranges)
                    arr[idx] = piece
                    covered += int(np.prod(piece.shape))
            if covered < int(np.prod(shape)):
                raise RuntimeError(
                    f"Sharded checkpoint for {model_id} is incomplete: "
                    f"{name} has {covered}/{int(np.prod(shape))} elements "
                    f"across {len(shards)} shard file(s) — all hosts' shard "
                    f"files must be visible to reassemble")
            out[name] = arr
        return out

    @classmethod
    def deserialize(cls, model_id: str) -> "NeuralNetworkModel":
        """Load a checkpoint, restoring dtypes exactly (reference:
        neural_net_model.py:124-174).  :raises KeyError: unknown model."""
        data = checkpoint.load(model_id)
        model = cls.__new__(cls)
        model.model_id = model_id
        model.layers_dsl = data["layers"]
        model.optimizer_config = data["optimizer"]
        model.arch = CompiledArch.get(model.layers_dsl)
        assembled = (cls._reassemble_sharded(model_id, data["sharded"],
                                             data.get("shard_tag"))
                     if data.get("sharded") else {})
        params = dict(data["params"])
        buffers = dict(data["buffers"])
        opt_leaves_in = data["opt_state_leaves"]
        if isinstance(opt_leaves_in, dict):
            opt_leaves = dict(opt_leaves_in)
        else:  # pre-sharding checkpoint format: plain list
            opt_leaves = dict(enumerate(opt_leaves_in))
        for name, arr in assembled.items():
            if name.startswith("__buf__"):
                buffers[name[len("__buf__"):]] = arr
            elif name.startswith("__opt__"):
                opt_leaves[int(name[len("__opt__"):])] = arr
            else:
                params[name] = arr
        model.params = {k: jnp.asarray(v) for k, v in params.items()}
        model.buffers = {k: jnp.asarray(v) for k, v in buffers.items()}
        # Buffer-schema migration: checkpoints written before a module
        # gained a buffer (e.g. MoE router_fraction) lack its key; training
        # would then grow the lax.scan carry mid-step and fail at trace
        # time.  Fill absent buffers with their module defaults.
        for mod in model.arch.mods:
            for sub in mod.walk():
                for key, value in sub.init_buffers().items():
                    model.buffers.setdefault(key, jnp.asarray(value))
        optimizer = dsl.build_optimizer(model.optimizer_config)
        template = jax.eval_shape(optimizer.init, model.params)
        model.opt_state = jax.tree.unflatten(
            jax.tree.structure(template),
            [jnp.asarray(opt_leaves[i]) for i in range(len(opt_leaves))])
        model.progress = data.get("progress", [])
        model.avg_cost = data.get("avg_cost")
        model.avg_cost_history = data.get("avg_cost_history", [])
        model.stats = data.get("stats")
        model.status = data.get("status", {"code": "Created", "message": None})
        model.device = None
        model._sample_rng = jax.random.key(0)
        model._pipe_layout = None
        return model

    @classmethod
    def delete(cls, model_id: str):
        checkpoint.delete(model_id)

    # -- HuggingFace import -------------------------------------------------

    @classmethod
    def from_huggingface(cls, model_id: str, hf_repo_id: str,
                         revision: Optional[str] = None,
                         device: Optional[str] = None
                         ) -> "NeuralNetworkModel":
        """Import GPT-2/Gemma weights into the flat param pytree as bf16
        (reference: neural_net_model.py:176-237).

        Torch-free: weights come from safetensors files via
        ``hf_loader`` (numpy arrays, no torch graph materialized — the
        reference routes through torch because it *is* torch); only the
        config is read through transformers.  Repos shipping nothing but
        torch ``.bin`` weights fall back to torch when it is installed.
        """
        import transformers
        from . import hf_loader

        local_dir = hf_loader.resolve_checkpoint_dir(hf_repo_id, revision)
        config = transformers.AutoConfig.from_pretrained(local_dir)
        sd = hf_loader.load_state_dict(local_dir)

        n_layer = Mapper.detect_hf_n_layer(sd)
        if not n_layer:
            cfg = getattr(config, "text_config", None) or config
            n_layer = int(getattr(cfg, "n_layer", 0)
                          or getattr(cfg, "num_hidden_layers", 0))
        layers = Mapper.from_hf_config(config, n_layer_override=n_layer)
        mapper = Mapper(layers, {"adamw": {"lr": 6e-4, "betas": [0.9, 0.95],
                                           "eps": 1e-8}})
        model = cls(model_id, mapper)
        mapped = Mapper.map_hf_state_dict_to_custom(sd, n_layer, config)

        expected = set(model.params)
        got = set(mapped)
        if expected != got:
            raise KeyError(f"HF state dict mismatch: missing "
                           f"{sorted(expected - got)}, unexpected "
                           f"{sorted(got - expected)}")
        for key, value in mapped.items():
            if tuple(value.shape) != tuple(model.params[key].shape):
                raise ValueError(f"Shape mismatch for {key}: HF "
                                 f"{tuple(value.shape)} vs model "
                                 f"{tuple(model.params[key].shape)}")
        model.params = {k: jnp.asarray(v, jnp.bfloat16)
                        for k, v in mapped.items()}
        model.opt_state = mapper.to_optimizer().init(model.params)
        model.to_device(device)
        model.status = {"code": "Imported",
                        "message": f"Imported from {hf_repo_id}"}
        model.serialize()
        return model


