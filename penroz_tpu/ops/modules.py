"""Functional layer modules for the JSON layer DSL.

Every module is a lightweight Python object that knows how to

- ``init(rng)``  -> flat dict of parameter arrays, and
- ``apply(x, ctx)`` -> output array,

where parameters live in a single flat ``{"layers.0.0.weight": Array}`` dict
whose key names mirror the reference implementation's torch ``state_dict``
naming (reference: neural_net_model.py:58, mappers.py:318-448).  Keeping the
flat naming makes checkpoint round-trips and HuggingFace weight mapping pure
table lookups, while the apply path stays a pure function that ``jax.jit`` can
trace once per shape.

Design notes (TPU-first):
- No module mutates state.  Batch-norm running statistics are "buffers" kept in
  a separate flat dict; updated values are written into ``ctx.buffer_updates``
  during trace and returned from the jitted caller.
- The KV cache is a pytree threaded through ``ctx.kv`` (see ops/kv_cache.py);
  attention layers never hold references to it (reference mutates modules:
  neural_net_layers.py:24-31).
- Position offsets are dynamic scalar arrays (``ctx.pos_offset``) so a single
  compiled decode step serves every generation position (reference mutates
  ``PositionEmbedding.position_offset``: neural_net_layers.py:98-118).
"""

from __future__ import annotations

import copy
import functools
import logging
import math
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from penroz_tpu.ops import attention as attn_ops
from penroz_tpu.utils import tracing

log = logging.getLogger(__name__)


class Stat(NamedTuple):
    """What a module reports of a training call: declared beside the module
    (:meth:`Module.stats`), reported through :meth:`Ctx.report`.  By these
    fields alone the epoch program accumulates it and returns it under its
    name, and the job's ``penroz/train_epoch`` span, its ``/progress/`` rows
    and ``GET /metrics`` show it."""

    name: str
    reduce: str          # "mean", "sum" or "max": how reports fold, over
    #                      the modules of a call and an epoch's micro-steps
    family: str          # on /metrics: ``penroz_train_<family>``
    #                      (utils/tracing.py::TRAIN_FAMILIES)
    shape: tuple = ()    # () a scalar; (n,) one value a pass
    host: type = float   # what it leaves the epoch as (``int``: a count)

    def fold(self, seen, value):
        """``seen`` after one more report: the larger for ``max``, the sum
        for ``sum`` and for ``mean``, which is the sum ÷ the reports."""
        return (jnp.maximum if self.reduce == "max" else jnp.add)(seen, value)

    def empty(self):
        """What folds to nothing: where an epoch's accumulator starts."""
        return jnp.full(self.shape, -jnp.inf if self.reduce == "max" else 0.0,
                        jnp.float32)

    def on_host(self, value):
        """An epoch's ``value`` on the host: ``host``, or a list a pass."""
        value = np.asarray(value, np.float64)
        return list(map(self.host, value)) if self.shape else self.host(value)


class Ctx:
    """Per-call context threaded through module application.

    Holds the parameter/buffer dicts plus dynamic state (PRNG key, KV cache,
    position offset).  Constructed fresh inside each jitted function, so its
    attributes may freely hold traced arrays.
    """

    def __init__(self, params, buffers=None, *, training=False, rng=None,
                 kv=None, pos_offset=None, compute_dtype=None, sp_mesh=None,
                 platform=None, sp_mode="ring", sp_manual_axis=None,
                 ep_mesh=None, lora=None, lora_idx=None, ragged_descs=None,
                 ragged_rows=None, targets=None):
        self.params = params
        self.buffers = buffers or {}
        self.training = training
        self.rng = rng
        self.kv = kv  # ops.kv_cache.KVState or None
        self.pos_offset = pos_offset  # scalar int32 array or None
        self.compute_dtype = compute_dtype
        self.sp_mesh = sp_mesh  # Mesh with a >1 'sequence' axis → SP attn
        self.sp_mode = sp_mode  # 'ring' (ppermute) | 'alltoall' (Ulysses)
        # Set when the caller is ALREADY inside a manual region binding the
        # sequence axis (GPipe schedule with seq manual): attention calls
        # the Ulysses body directly instead of wrapping its own shard_map.
        self.sp_manual_axis = sp_manual_axis
        # Mesh with a >1 'expert' axis → MoE capacity dispatch routes
        # tokens via lax.all_to_all over it instead of the dense-combine
        # psum (set only on the non-pipelined path: nesting an
        # expert-manual shard_map inside the GPipe schedule's manual
        # region is rejected by the Shardy partitioner — "manual axes
        # must come before free axes" on propagated dim shardings — so
        # MoE under pipe keeps the dense-combine inside each stage).
        self.ep_mesh = ep_mesh
        self.platform = platform  # execution platform hint for kernel gates
        # Mixed-adapter LoRA (models/lora.py): ``lora`` maps a Linear's
        # prefix to stacked low-rank factors {a: (L, r, in), b: (L, out, r),
        # scale: (L,)} and ``lora_idx`` (B,) selects each batch row's slot
        # (the last, all-zero slot is the base-model row).  Single-adapter
        # application instead BINDS ``<prefix>.lora_A/B/scale`` keys into
        # ``params`` — Linear.apply picks either up.
        self.lora = lora
        self.lora_idx = lora_idx
        # Ragged unified dispatch (paged caches only): ``ragged_descs`` is
        # the (NB, 4) packed-batch descriptor array (ops/kv_cache.py::
        # build_descriptors) and ``ragged_rows`` the per-packed-token pool
        # scatter rows (PagedKVState.packed_rows — computed once, shared by
        # every layer's append).  When set, attention appends/attends
        # through the packed path and ``pos_offset`` holds the (1, Tp)
        # per-token absolute positions.
        self.ragged_descs = ragged_descs
        self.ragged_rows = ragged_rows
        # A stack with several exits (:class:`Looped`) takes its loss from
        # each of them: given ``targets`` it leaves every exit's per-token
        # cross-entropy and gate logit in ``exits`` for the model's cost.
        # ``layer_offset`` is added to an attention layer's cache slot: the
        # pass of the loop the layer is being applied in, times the slots
        # a pass holds.
        self.targets = targets
        self.exits = None
        self.layer_offset = 0
        # by name, what the modules reported: (Stat, folded value, reports)
        self.stats = {}
        self.buffer_updates = {}
        self.aux_losses = []  # auxiliary training losses (e.g. MoE balance)
        self._rng_counter = 0

    def next_rng(self):
        if self.rng is None:
            raise ValueError("PRNG key required (dropout in training mode)")
        self._rng_counter += 1
        return jax.random.fold_in(self.rng, self._rng_counter)

    def report(self, stat: Stat, value, reports: int = 1):
        """Fold ``value`` into what the call reports as ``stat`` by its rule;
        float32, and no gradient flows through it.  ``reports``: how many
        ``value`` folds already (:func:`_recomputed`'s hand-over)."""
        value = jax.lax.stop_gradient(value.astype(jnp.float32))
        if stat.name in self.stats:
            _, seen, n = self.stats[stat.name]
            value, reports = stat.fold(seen, value), n + reports
        self.stats[stat.name] = (stat, value, reports)

    def reported(self) -> dict:
        """``{name: value}`` of what the call reported."""
        return {name: value / n if stat.reduce == "mean" else value
                for name, (stat, value, n) in self.stats.items()}

    def offset(self):
        """Current sequence position offset (0 when no cache attached)."""
        if self.pos_offset is not None:
            return self.pos_offset
        if self.kv is not None:
            return self.kv.length
        return jnp.zeros((), jnp.int32)


class Module:
    """Base class for DSL layer modules."""

    prefix: str = ""

    def bind(self, prefix: str):
        """Assign the flat-dict key prefix for this module's parameters."""
        self.prefix = prefix
        for name, child in self.children():
            child.bind(f"{prefix}.{name}" if prefix else name)
        return self

    def children(self) -> Sequence[tuple[str, "Module"]]:
        return ()

    def walk(self):
        """Yield self and all descendant modules depth-first."""
        yield self
        for _, child in self.children():
            yield from child.walk()

    def key(self, name: str) -> str:
        return f"{self.prefix}.{name}" if self.prefix else name

    # -- parameters ---------------------------------------------------------
    def init(self, rng) -> dict[str, jax.Array]:
        """Default torch-equivalent initialization of own (non-child) params."""
        return {}

    def init_buffers(self) -> dict[str, jax.Array]:
        return {}

    def param_shapes(self) -> dict[str, tuple]:
        """Shapes of own (non-child) trainable parameters."""
        return {}

    def end_step(self, buffers: dict) -> dict[str, jax.Array]:
        """Updates of own buffers made once an optimizer step, after its
        last micro-step (``CompiledArch.end_step``), from the buffers as the
        micro-steps left them."""
        return {}

    def stats(self) -> Sequence[Stat]:
        """What this module reports of every training call
        (:meth:`Ctx.report`), its children's apart."""
        return ()

    # -- application --------------------------------------------------------
    def apply(self, x, ctx: Ctx):
        raise NotImplementedError

    def _p(self, ctx: Ctx, name: str):
        p = ctx.params[self.key(name)]
        if ctx.compute_dtype is not None and jnp.issubdtype(p.dtype, jnp.floating):
            p = p.astype(ctx.compute_dtype)
        return p


@functools.lru_cache(maxsize=None)
def _log_plan(kind: str, **plan) -> None:
    """Once per distinct plan of the process."""
    log.info("%s plan: %s", kind,
             " ".join(f"{k}={v}" for k, v in plan.items()))


def _record_plan(kind: str, **plan) -> None:
    """As ``penroz/flash_plan``: an INFO line ``<kind> plan: …`` per distinct
    plan and, each time a program traces the module, a
    ``penroz/<kind>_plan`` span under whatever span is compiling."""
    _log_plan(kind, **plan)
    with tracing.span(f"penroz/{kind}_plan", **plan):
        pass


def _uniform(rng, shape, bound, dtype=jnp.float32):
    return jax.random.uniform(rng, shape, dtype, minval=-bound, maxval=bound)


# ---------------------------------------------------------------------------
# Leaf layers
# ---------------------------------------------------------------------------

_GATHER_BWD_CHUNK = 1024


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _gather_rows(table, ids, num_rows: int, dtype_name: str, platform=None):
    """``table[ids]`` whose backward sums in fp32 (``platform``: the
    placement hint, whose mesh decides how: :func:`_gather_rows_bwd`)."""
    return jnp.take(table, ids, axis=0)


def _gather_rows_fwd(table, ids, num_rows: int, dtype_name: str, platform):
    return jnp.take(table, ids, axis=0), ids


def _scatter_rows_grad(ids, g, num_rows: int, dtype):
    """XLA's own scatter-add into an fp32 table, rounded once: on a v5e a
    native ``scatter`` fusion with no loop, 0.86 ms for 12 288 rows of 768
    into GPT-2's 50 304, whatever the ids (PERF.md §6, PR 30)."""
    d = g.shape[-1]
    dw = jnp.zeros((num_rows, d), jnp.float32).at[ids.reshape(-1)].add(
        g.reshape(-1, d).astype(jnp.float32))
    return dw.astype(dtype)


def _onehot_rows_grad(ids, g, num_rows: int, dtype):
    """one-hotᵀ @ g over the whole table — 2·V·N·d FLOPs to add N rows —
    with id-chunks streamed through a scan so the transient one-hot operand
    stays at (num_rows, chunk) instead of a full (num_rows, B·T) buffer in
    HBM."""
    flat_ids = ids.reshape(-1)
    d = g.shape[-1]
    gf = g.reshape(-1, d)
    chunk = min(_GATHER_BWD_CHUNK, flat_ids.shape[0])
    pad = -flat_ids.shape[0] % chunk
    if pad:
        # -1 ids produce an all-zero one-hot column → no grad contribution.
        flat_ids = jnp.pad(flat_ids, (0, pad), constant_values=-1)
        gf = jnp.pad(gf, ((0, pad), (0, 0)))
    idc = flat_ids.reshape(-1, chunk)
    gc = gf.reshape(-1, chunk, d)

    def step(acc, ch):
        cid, cg = ch
        onehot = jax.nn.one_hot(cid, num_rows, dtype=cg.dtype, axis=0)
        # fp32 MXU accumulation — a bf16 product would round each chunk's
        # per-id gradient sum to 8 mantissa bits before the fp32 carry add.
        return acc + jnp.matmul(onehot, cg,
                                preferred_element_type=jnp.float32), None

    acc0 = jnp.zeros((num_rows, d), jnp.float32)
    dw, _ = jax.lax.scan(step, acc0, (idc, gc))
    return dw.astype(dtype)


@functools.lru_cache(maxsize=None)
def _log_grad_plan(num_rows: int, n: int, d: int, path: str) -> None:
    """Once per distinct (shape, path) of the process."""
    log.info("embedding grad plan: V=%d N=%d d=%d path=%s", num_rows, n, d,
             path)


def _gather_rows_bwd(num_rows: int, dtype_name: str, platform, ids, g):
    """Sum the rows of ``g`` by id in fp32, rounded once to the table's
    dtype (``jnp.take``'s own VJP would add in the table's dtype: under
    bf16 compute a rounding at every repeated id).

    On one device: XLA's scatter.  (It once lowered to a serialized loop on
    a TPU, which is why the one-hot matmul was written; it no longer does,
    and the matmul multiplies the whole vocabulary by every token.)  Under a
    mesh: the one-hot scan, as before — GSPMD gathers the ids and rows for
    it (N·d values), where it would give the scatter an all-reduce of the
    partial (V, d) tables, in the table's dtype; not measured on four chips.

    Which path a traced program got is counted: an INFO line per distinct
    shape and a ``penroz/embed_grad_plan`` span under whatever span is
    compiling (a /train/ job's first epochs, beside ``penroz/flash_plan``).
    """
    n, d = int(np.prod(ids.shape)), g.shape[-1]
    path, grad = (("onehot_scan", _onehot_rows_grad)
                  if isinstance(platform, attn_ops.Placement)
                  else ("scatter", _scatter_rows_grad))
    _log_grad_plan(num_rows, n, d, path)
    with tracing.span("penroz/embed_grad_plan", V=num_rows, N=n, d=d,
                      path=path):
        pass
    return (grad(ids, g, num_rows, jnp.dtype(dtype_name)),
            np.zeros(ids.shape, dtype=jax.dtypes.float0))


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


class Embedding(Module):
    def __init__(self, num_embeddings: int, embedding_dim: int):
        self.num_embeddings = int(num_embeddings)
        self.embedding_dim = int(embedding_dim)

    def param_shapes(self):
        return {"weight": (self.num_embeddings, self.embedding_dim)}

    def init(self, rng):
        w = jax.random.normal(rng, (self.num_embeddings, self.embedding_dim), jnp.float32)
        return {self.key("weight"): w}

    def apply(self, x, ctx):
        w = self._p(ctx, "weight")
        if attn_ops._tpu_platform(w, ctx.platform):
            # TPU (bf16 compute): fp32-summed backward, _gather_rows_bwd.
            return _gather_rows(w, x, self.num_embeddings, w.dtype.name,
                                ctx.platform)
        return jnp.take(w, x, axis=0)  # CPU scatter-add VJP is fine


class ScaledEmbedding(Embedding):
    """Embedding whose output is scaled by a constant (Gemma sqrt(d) scale)."""

    def __init__(self, num_embeddings: int, embedding_dim: int, scale: float = 1.0):
        super().__init__(num_embeddings, embedding_dim)
        self.scale = float(scale)

    def apply(self, x, ctx):
        out = super().apply(x, ctx)
        return out * jnp.asarray(self.scale, out.dtype)


class PositionEmbedding(Embedding):
    """Learned position embedding indexed from the dynamic context offset.

    The reference mutates a ``position_offset`` attribute during cached decode
    (neural_net_layers.py:98-118); here the offset is a traced scalar from the
    Ctx so one compiled program covers all positions.
    """

    def apply(self, x, ctx):
        num_positions = x.shape[-1]
        # Per-index clamping (jnp.take) — a dynamic slice would shift the
        # whole window on overflow, corrupting still-valid positions.  The
        # scatter in this VJP touches at most num_positions contiguous rows,
        # which XLA handles fine.  A (B,) offset (ragged batches) yields
        # per-sequence position rows (B, T) → (B, T, d).
        offset = jnp.asarray(ctx.offset())
        steps = jnp.arange(num_positions, dtype=jnp.int32)
        if offset.ndim == 2:
            # (B, T) explicit per-token absolute positions (ragged packed
            # batches) — already fully resolved, nothing to add.
            positions = offset
        elif offset.ndim >= 1:
            positions = offset[:, None] + steps
        else:
            positions = offset + steps
        return jnp.take(self._p(ctx, "weight"), positions, axis=0)


class Linear(Module):
    """Dense layer storing weight as (out, in) for state-dict parity."""

    def __init__(self, in_features: int, out_features: int, bias: bool = True):
        self.in_features = int(in_features)
        self.out_features = int(out_features)
        self.use_bias = bool(bias)

    def param_shapes(self):
        shapes = {"weight": (self.out_features, self.in_features)}
        if self.use_bias:
            shapes["bias"] = (self.out_features,)
        return shapes

    def init(self, rng):
        kw, kb = jax.random.split(rng)
        bound = 1.0 / math.sqrt(self.in_features)
        params = {self.key("weight"): _uniform(kw, (self.out_features, self.in_features), bound)}
        if self.use_bias:
            params[self.key("bias")] = _uniform(kb, (self.out_features,), bound)
        return params

    def apply(self, x, ctx):
        w = self._p(ctx, "weight")
        out = jnp.matmul(x, w.T)
        if self.use_bias:
            out = out + self._p(ctx, "bias")
        return self._maybe_lora(out, x, ctx)

    def _maybe_lora(self, out, x, ctx):
        """Low-rank adapter delta ``out += scale · (x Aᵀ) Bᵀ`` when adapter
        factors are bound for this projection (models/lora.py).

        Two bindings: flat ``<prefix>.lora_A/B/scale`` keys inside
        ``ctx.params`` apply ONE adapter to the whole batch (training, the
        legacy generate paths); ``ctx.lora[prefix]`` holds per-slot stacked
        factors and ``ctx.lora_idx`` routes each batch row to its slot —
        the BGMV-style gathered einsum that lets rows with different
        adapters (or none: the trailing all-zero slot) share one forward.
        """
        a = ctx.params.get(self.key("lora_A"))
        if a is not None:
            b = ctx.params[self.key("lora_B")]
            s = ctx.params[self.key("lora_scale")]
            t = jnp.matmul(x, a.astype(x.dtype).T)
            return out + jnp.matmul(t, b.astype(x.dtype).T) \
                * s.astype(out.dtype)
        ent = ctx.lora.get(self.prefix) if ctx.lora else None
        if ent is None:
            return out
        idx = ctx.lora_idx
        if idx is not None and jnp.ndim(idx) == 2:
            # (B, T) PER-TOKEN slots — the ragged packed batch, where
            # adjacent tokens belong to different rows with different
            # adapters.  Gathered factors grow a token axis; otherwise
            # identical to the per-row einsum below.
            asel = jnp.take(ent["a"], idx, axis=0).astype(x.dtype)
            bsel = jnp.take(ent["b"], idx, axis=0).astype(x.dtype)
            ssel = jnp.take(ent["scale"], idx, axis=0).astype(out.dtype)
            t = jnp.einsum("btd,btrd->btr", x, asel)
            return out + jnp.einsum("btr,btor->bto", t, bsel) \
                * ssel[:, :, None]
        asel = jnp.take(ent["a"], idx, axis=0).astype(x.dtype)  # (B, r, in)
        bsel = jnp.take(ent["b"], idx, axis=0).astype(x.dtype)  # (B, out, r)
        ssel = jnp.take(ent["scale"], idx, axis=0).astype(out.dtype)  # (B,)
        if x.ndim == 2:  # (B, d) stacks (MLP-style models)
            t = jnp.einsum("bd,brd->br", x, asel)
            return out + jnp.einsum("br,bor->bo", t, bsel) * ssel[:, None]
        t = jnp.einsum("btd,brd->btr", x, asel)
        return out + jnp.einsum("btr,bor->bto", t, bsel) \
            * ssel[:, None, None]


class Flatten(Module):
    def __init__(self, start_dim: int = 1, end_dim: int = -1):
        self.start_dim = start_dim
        self.end_dim = end_dim

    def apply(self, x, ctx):
        start = self.start_dim if self.start_dim >= 0 else x.ndim + self.start_dim
        end = self.end_dim if self.end_dim >= 0 else x.ndim + self.end_dim
        shape = x.shape[:start] + (-1,) + x.shape[end + 1:]
        return jnp.reshape(x, shape)


class BatchNorm1d(Module):
    def __init__(self, num_features: int, eps: float = 1e-5, momentum: float = 0.1):
        self.num_features = int(num_features)
        self.eps = float(eps)
        self.momentum = float(momentum)

    def param_shapes(self):
        return {"weight": (self.num_features,), "bias": (self.num_features,)}

    def init(self, rng):
        return {self.key("weight"): jnp.ones((self.num_features,), jnp.float32),
                self.key("bias"): jnp.zeros((self.num_features,), jnp.float32)}

    def init_buffers(self):
        return {self.key("running_mean"): jnp.zeros((self.num_features,), jnp.float32),
                self.key("running_var"): jnp.ones((self.num_features,), jnp.float32),
                self.key("num_batches_tracked"): jnp.zeros((), jnp.int64
                                                           if jax.config.jax_enable_x64 else jnp.int32)}

    def apply(self, x, ctx):
        w, b = self._p(ctx, "weight"), self._p(ctx, "bias")
        reduce_axes = tuple(i for i in range(x.ndim) if i != 1) if x.ndim > 2 else (0,)
        if ctx.training:
            mean = jnp.mean(x, axis=reduce_axes)
            var = jnp.var(x, axis=reduce_axes)
            n = x.size // x.shape[1]
            unbiased = var * (n / max(n - 1, 1))
            rm = ctx.buffers[self.key("running_mean")]
            rv = ctx.buffers[self.key("running_var")]
            nb = ctx.buffers[self.key("num_batches_tracked")]
            m = self.momentum
            ctx.buffer_updates[self.key("running_mean")] = (1 - m) * rm + m * mean
            ctx.buffer_updates[self.key("running_var")] = (1 - m) * rv + m * unbiased
            ctx.buffer_updates[self.key("num_batches_tracked")] = nb + 1
        else:
            mean = ctx.buffers[self.key("running_mean")]
            var = ctx.buffers[self.key("running_var")]
        shape = (1, -1) + (1,) * (x.ndim - 2)
        mean, var = mean.reshape(shape), var.reshape(shape)
        inv = jax.lax.rsqrt(var + self.eps)
        return (x - mean) * inv * w.reshape(shape) + b.reshape(shape)


class LayerNorm(Module):
    def __init__(self, normalized_shape, eps: float = 1e-5, bias: bool = True,
                 elementwise_affine: bool = True):
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(int(d) for d in normalized_shape)
        self.eps = float(eps)
        # Non-parametric mode (OLMo v1): normalize only, no learned scale
        # or shift (torch LayerNorm(elementwise_affine=False)).
        self.affine = bool(elementwise_affine)
        self.use_bias = bool(bias) and self.affine

    def param_shapes(self):
        if not self.affine:
            return {}
        shapes = {"weight": self.normalized_shape}
        if self.use_bias:
            shapes["bias"] = self.normalized_shape
        return shapes

    def init(self, rng):
        if not self.affine:
            return {}
        params = {self.key("weight"): jnp.ones(self.normalized_shape, jnp.float32)}
        if self.use_bias:
            params[self.key("bias")] = jnp.zeros(self.normalized_shape, jnp.float32)
        return params

    def apply(self, x, ctx):
        # fp32-internal normalization like torch F.layer_norm (and HF's
        # OlmoLayerNorm, which upcasts explicitly): bf16 mean/var over the
        # large pre-norm activations would drift imported-model numerics.
        axes = tuple(range(x.ndim - len(self.normalized_shape), x.ndim))
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=axes, keepdims=True)
        var = jnp.var(xf, axis=axes, keepdims=True)
        out = ((xf - mean) * jax.lax.rsqrt(var + self.eps)).astype(x.dtype)
        if self.affine:
            out = out * self._p(ctx, "weight")
        if self.use_bias:
            out = out + self._p(ctx, "bias")
        return out


class Softcap(Module):
    """Gemma-2 logit soft-capping: ``cap · tanh(x / cap)`` (HF applies it
    to the lm-head output via ``final_logit_softcapping``)."""

    def __init__(self, cap: float):
        if float(cap) <= 0.0:
            raise ValueError(f"softcap must be > 0, got {cap}")
        self.cap = float(cap)

    def apply(self, x, ctx):
        return (self.cap * jnp.tanh(x.astype(jnp.float32) / self.cap)
                ).astype(x.dtype)


class Clamp(Module):
    """Elementwise value clipping (OLMo v1 ``clip_qkv``: the fused QKV
    projection output is clamped to ±clip before attention)."""

    def __init__(self, min: Optional[float] = None,
                 max: Optional[float] = None):
        if min is None and max is None:
            raise ValueError("clamp needs at least one of min/max")
        self.min = float(min) if min is not None else None
        self.max = float(max) if max is not None else None

    def apply(self, x, ctx):
        return jnp.clip(x, self.min, self.max)


class RMSNorm(Module):
    """RMS normalization computed internally in float32 (reference:
    neural_net_layers.py:144-155)."""

    def __init__(self, normalized_shape: int, eps: float = 1e-6):
        self.normalized_shape = int(normalized_shape)
        self.eps = float(eps)

    def param_shapes(self):
        return {"weight": (self.normalized_shape,)}

    def init(self, rng):
        return {self.key("weight"): jnp.ones((self.normalized_shape,), jnp.float32)}

    def apply(self, x, ctx):
        dtype = x.dtype
        xf = x.astype(jnp.float32)
        norm = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + self.eps)
        return (xf * norm).astype(dtype) * self._p(ctx, "weight")


class ReLU(Module):
    def apply(self, x, ctx):
        return jax.nn.relu(x)


class GELU(Module):
    def __init__(self, approximate: str = "none"):
        self.approximate = approximate

    def apply(self, x, ctx):
        return jax.nn.gelu(x, approximate=(self.approximate == "tanh"))


class SiLU(Module):
    def apply(self, x, ctx):
        return jax.nn.silu(x)


class Sigmoid(Module):
    def apply(self, x, ctx):
        return jax.nn.sigmoid(x)


class Tanh(Module):
    def apply(self, x, ctx):
        return jnp.tanh(x)


class Softmax(Module):
    def __init__(self, dim: Optional[int] = None):
        self.dim = dim

    def apply(self, x, ctx):
        return jax.nn.softmax(x, axis=self.dim if self.dim is not None else -1)


class SoftmaxOnLast(Softmax):
    """Softmax over the vocabulary of only the final sequence position."""

    def apply(self, x, ctx):
        return jax.nn.softmax(x[:, -1, :], axis=self.dim if self.dim is not None else -1)


class Dropout(Module):
    def __init__(self, p: float = 0.5):
        self.p = float(p)

    def apply(self, x, ctx):
        if not ctx.training or self.p == 0.0:
            return x
        keep = 1.0 - self.p
        mask = jax.random.bernoulli(ctx.next_rng(), keep, x.shape)
        return jnp.where(mask, x / keep, jnp.zeros((), x.dtype))


# ---------------------------------------------------------------------------
# Containers
# ---------------------------------------------------------------------------

class Sequential(Module):
    def __init__(self, *layers: Module):
        self.layers = list(layers)

    def children(self):
        return [(str(i), l) for i, l in enumerate(self.layers)]

    def apply(self, x, ctx):
        for layer in self.layers:
            x = layer.apply(x, ctx)
        return x


class Summation(Sequential):
    """Sum of each child applied to the same input (token+position embed)."""

    def apply(self, x, ctx):
        out = self.layers[0].apply(x, ctx)
        for layer in self.layers[1:]:
            out = out + layer.apply(x, ctx)
        return out


class ResidualConnection(Sequential):
    """x = x + child(x), applied for each child in order."""

    def apply(self, x, ctx):
        for layer in self.layers:
            x = x + layer.apply(x, ctx)
        return x


class ParallelResidual(Sequential):
    """x = x + Σ child(x): every child reads the SAME input.

    The GPT-NeoX/Pythia ``use_parallel_residual`` block — attention and MLP
    branches run on the same pre-block activations and their outputs are
    summed onto the residual stream (HF ``modeling_gpt_neox`` forward),
    unlike :class:`ResidualConnection` where each child sees the previous
    child's residual sum.

    Composable as ``residual([summation([...branches])])``, but the
    dedicated container keeps branch params one level flatter
    (``layers.i.{branch}.*``), which the NeoX HF key remap relies on.
    """

    def apply(self, x, ctx):
        out = x
        for layer in self.layers:
            out = out + layer.apply(x, ctx)
        return out


class TransformerBlock(Module):
    """Pre-norm decoder block with optional Gemma-style post-norms.

    ``post_norm_on_residual=True`` (Gemma 3+): ``h = post_norm(x + branch(x))``;
    ``False`` (Gemma 2): ``h = x + post_norm(branch(x))``.
    (reference: neural_net_layers.py:188-225)
    """

    def __init__(self, attn_block: Module, mlp_block: Module,
                 post_attn_norm: Module = None, post_mlp_norm: Module = None,
                 post_norm_on_residual: bool = True):
        self.attn_block = attn_block
        self.mlp_block = mlp_block
        self.post_attn_norm = post_attn_norm
        self.post_mlp_norm = post_mlp_norm
        self.post_norm_on_residual = bool(post_norm_on_residual)

    def children(self):
        out = [("attn_block", self.attn_block), ("mlp_block", self.mlp_block)]
        if self.post_attn_norm is not None:
            out.append(("post_attn_norm", self.post_attn_norm))
        if self.post_mlp_norm is not None:
            out.append(("post_mlp_norm", self.post_mlp_norm))
        return out

    def apply(self, x, ctx):
        attn_out = self.attn_block.apply(x, ctx)
        if self.post_attn_norm is not None and not self.post_norm_on_residual:
            attn_out = self.post_attn_norm.apply(attn_out, ctx)
        h = x + attn_out
        if self.post_attn_norm is not None and self.post_norm_on_residual:
            h = self.post_attn_norm.apply(h, ctx)

        mlp_out = self.mlp_block.apply(h, ctx)
        if self.post_mlp_norm is not None and not self.post_norm_on_residual:
            mlp_out = self.post_mlp_norm.apply(mlp_out, ctx)
        out = h + mlp_out
        if self.post_mlp_norm is not None and self.post_norm_on_residual:
            out = self.post_mlp_norm.apply(out, ctx)
        return out


def record_hc_plan(sub_blocks: Sequence["HyperConnected"], x, training: bool,
                   platform=None, itemsize: int = 4):
    """The plans (:func:`_record_plan`) of a traced program of a model with a
    multi-stream residual, for all of its sub-blocks on tokens ``x`` ``(B,
    T)``.  ``hc``: ``recomputed_sub_blocks`` are those whose inside the
    backward runs again (all of them in training, :func:`_recomputed`).
    ``hc_mix``: the path the mixing's passes over the state take
    (:meth:`HyperConnected.mix_plan`)."""
    first, (batch, seq) = sub_blocks[0], x.shape[:2]
    _record_plan("hc", streams=first.streams,
                 sinkhorn_iters=first.sinkhorn_iters,
                 sub_blocks=len(sub_blocks), tokens=batch * seq,
                 recomputed_sub_blocks=len(sub_blocks) if training else 0)
    _record_plan("hc_mix", **first.mix_plan(
        batch, seq, _one_tpu(x, platform), itemsize))


def _one_tpu(x, platform) -> bool:
    """Whether ``x``'s program runs on a TPU and on one device: under a mesh
    a Mosaic call would have to be mapped over the shards
    (``ops/attention.py::_on_shards``) and the mixing's weight gradient
    summed over them, where the same passes in ``jnp`` partition like any
    XLA code."""
    return (getattr(platform, "mesh", None) is None
            and attn_ops._tpu_platform(x, platform))


def sinkhorn(logits, iters: int, eps: float):
    """``exp(logits)`` made doubly stochastic over its first two axes
    ``(n, n, ...)``: ``iters`` times its columns, then its rows, each divided
    by its sum + ``eps``.  Differentiated through every iteration."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


class _HcMaps(NamedTuple):
    """What a stream mixing is made with: the three maps' numbers, and the
    ``path`` its passes over the state take (``fused``: :func:`_token_mix`,
    XLA's; ``kernel``: ``ops/pallas/hc_mix.py``; ``interpret``: that kernel
    interpreted, the tests')."""
    streams: int
    features: int
    iters: int
    eps: float
    hc_eps: float
    clamp: tuple
    path: str = "fused"


def _token_mix(groups, plan, coef=None, rows=None, w=None):
    """``ops/pallas/hc_mix.py::token_mix`` in ``jax.numpy``: the same pass
    over the same stream-major groups ``(B, g, T, d)``, what is a few
    numbers a token ``(B, ·, T)`` with the tokens on the minor axis, for XLA
    to fuse."""
    d, out_dtype = groups[0].shape[3], groups[0].dtype
    slabs = [g[:, j] for g in groups for j in range(g.shape[1])]
    wide = {}

    def f32(s):
        if s not in wide:
            wide[s] = slabs[s].astype(jnp.float32)
        return wide[s]

    of = lambda j: w[:, j * d:(j + 1) * d]
    back = iter(plan.back)
    results = []
    for group in plan.outs:
        made = []
        for terms in group:
            acc = sum(f32(s) if c is None else coef[:, c, :, None] * f32(s)
                      for c, s in terms)
            stream = next(back, None)
            if stream is not None:
                acc = acc + jnp.einsum("bmt,md->btd", rows,
                                       of(stream).astype(jnp.float32))
            made.append(acc)
        results.append(jnp.stack(made, axis=1).astype(out_dtype))
    if plan.dots:
        results.append(jnp.stack([jnp.sum(f32(a) * f32(b), axis=-1)
                                  for a, b in plan.dots], axis=1))
    if plan.proj:
        results.append(sum(
            jnp.einsum("btd,md->bmt", slabs[s], of(j),
                       preferred_element_type=jnp.float32)
            for j, s in enumerate(plan.proj)))
    if plan.wgrad:
        results.append(jnp.concatenate([
            jnp.einsum("bmt,btd->md", rows, slabs[s],
                       preferred_element_type=jnp.float32)
            for s in plan.wgrad], axis=1))
    return results


def _hc_pass(path: str, groups, coef=None, rows=None, w=None, **plan):
    """One pass over the state, ``plan`` the fields of
    ``ops/pallas/hc_mix.py::Pass``: the kernel's or XLA's."""
    from penroz_tpu.ops.pallas import hc_mix
    plan = hc_mix.Pass(**plan)
    if path == "fused":
        return _token_mix(groups, plan, coef, rows, w)
    return hc_mix.token_mix(groups, plan, coef=coef, rows=rows, w=w,
                            interpret=path == "interpret")


def _hc_rows(t, like):
    """Maps ``(k, tokens)`` as the passes' coefficients ``(B, k, T)`` beside
    ``like`` ``(B, T, ...)``: the tokens stay on the minor axis."""
    return jnp.moveaxis(t.reshape(t.shape[:1] + like.shape[:2]), 0, 1)


def _hc_lanes(t):
    """A pass's per-token results ``(B, k, T)`` as ``(k, tokens)``."""
    return jnp.moveaxis(t, 1, 0).reshape(t.shape[1], -1)


def _hc_stats(path: str, Xs, phi):
    """A token's statistics of the state ``Xs`` ``(B, n, T, d)`` in float32,
    one pass: its sum of squares ``(tokens,)`` and ``Phi vec(X)`` ``(maps,
    tokens)`` as ``n`` products of a stream ``(T, d)`` with its ``d``
    columns of ``Phi`` summed: ``X`` is never laid out as ``(B·T, n·d)``."""
    n = Xs.shape[1]
    squares, P = _hc_pass(path, [Xs], w=phi, proj=tuple(range(n)),
                          dots=tuple((j, j) for j in range(n)))
    return jnp.sum(squares, axis=1).reshape(-1), _hc_lanes(P)


def _hc_maps(cfg: _HcMaps, s, P, alpha, bias):
    """``(H_pre (n, tokens), H_post (n, tokens), H_res (n, n, tokens))`` of
    a token's statistics (:func:`_hc_stats`): 24 numbers a token at n = 4,
    the tokens on the minor axis."""
    n = cfg.streams
    # the token's 1/rms scales the product: x̃ itself is never written
    u = P * jax.lax.rsqrt(s / (n * cfg.features) + cfg.eps)
    bias = bias[:, None]
    pre = jax.nn.sigmoid(alpha[0] * u[:n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * u[n:2 * n] + bias[n:2 * n])
    res = jnp.clip(alpha[2] * u[2 * n:] + bias[2 * n:], *cfg.clamp)
    # (n·n, tokens) to (n, n, tokens) by rows and not by a reshape: the
    # kernel's results lie in tiles of 8 rows, and XLA carried that layout
    # through a reshape into Sinkhorn, where every iteration then paid a
    # broadcast and a relayout of their own (2 000 instructions a program)
    return pre, post, sinkhorn(jnp.stack(jnp.split(res, n)), cfg.iters,
                               cfg.hc_eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _hc_read(cfg: _HcMaps, X, phi, alpha, bias):
    """The reading half of a stream mixing: ``(x_in, H_post, H_res, X)`` of
    the state ``X`` ``(B, T, n, d)``, ``x_in = Σ_i H_pre[i] X_i`` in ``X``'s
    type.  Two passes over ``X``: the statistics; ``x_in``.  ``X`` is handed
    on as it came, for :func:`_hc_write`: what that finds for it in the
    backward arrives here and is added in the pass that writes ``dX``
    (:func:`_hc_read_bwd`)."""
    return _hc_read_fwd(cfg, X, phi, alpha, bias)[0]


def _hc_read_fwd(cfg, X, phi, alpha, bias):
    n, Xs = cfg.streams, jnp.swapaxes(X, 1, 2)
    (pre, post, res), pull = jax.vjp(
        functools.partial(_hc_maps, cfg), *_hc_stats(cfg.path, Xs, phi),
        alpha, bias)
    x_in, = _hc_pass(cfg.path, [Xs], coef=_hc_rows(pre, X),
                     outs=((tuple((i, i) for i in range(n)),),))
    return (x_in[:, 0], post, res, X), (X, phi, pre, pull)


def _hc_read_bwd(cfg, kept, cotangents):
    """Two passes over ``X``: ``dH_pre[i] = <dx_in, X_i>``; then, with the
    maps' cotangent taken through every Sinkhorn iteration (24 numbers a
    token, XLA's: ``pull``, the pull-back the forward made), ``dX_j =
    H_pre[j] dx_in + 2 ds X_j + dP Phi_j + dX_j of the writing half``,
    summed in float32 and written once, with ``dPhi_j = dP X_j`` summed
    over the tokens in the same pass."""
    X, phi, pre, pull = kept
    dx_in, dpost, dres, dX_write = cotangents
    n, Xs, g = cfg.streams, jnp.swapaxes(X, 1, 2), dx_in[:, None]
    dots, = _hc_pass(cfg.path, [g, Xs],
                     dots=tuple((0, 1 + i) for i in range(n)))
    ds, dP, dalpha, dbias = pull((_hc_lanes(dots), dpost, dres))
    dXs, dphi = _hc_pass(
        cfg.path, [g, Xs, jnp.swapaxes(dX_write, 1, 2)],
        coef=_hc_rows(jnp.concatenate([pre, 2.0 * ds[None]]), X),
        rows=_hc_rows(dP, X), w=phi,
        outs=(tuple(((j, 0), (n, 1 + j), (None, 1 + n + j))
                    for j in range(n)),),
        back=tuple(range(n)), wgrad=tuple(1 + j for j in range(n)))
    return jnp.swapaxes(dXs, 1, 2), dphi.astype(phi.dtype), dalpha, dbias


_hc_read.defvjp(_hc_read_fwd, _hc_read_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _hc_write(path: str, reduce: bool, X, y, post, res):
    """The writing half: ``X'_i = Σ_j H_res[i, j] X_j + H_post[i] y`` in
    ``X``'s type (``reduce``: their sum over ``i``, ``(B, T, d)``), one pass
    that reads ``X`` and ``y``; the backward (:func:`_hc_write_bwd`) one
    more."""
    return _hc_write_fwd(path, reduce, X, y, post, res)[0]


def _hc_write_coef(X, post, res):
    """Row ``i·n + j``: ``H_res[i, j]``; row ``n² + i``: ``H_post[i]``."""
    return _hc_rows(jnp.concatenate([*res, post]), X)


def _hc_write_fwd(path, reduce, X, y, post, res):
    n = X.shape[2]
    new = [tuple((i * n + j, j) for j in range(n)) + ((n * n + i, n),)
           for i in range(n)]
    out, = _hc_pass(path, [jnp.swapaxes(X, 1, 2), y[:, None]],
                    coef=_hc_write_coef(X, post, res),
                    outs=((sum(new, ()),) if reduce else tuple(new),))
    return (out[:, 0] if reduce else jnp.swapaxes(out, 1, 2)), (X, y, post,
                                                                res)


def _hc_write_bwd(path, reduce, kept, dout):
    """One pass over ``dX'``, ``X`` and ``y``: ``dy = Σ_i H_post[i] dX'_i``,
    ``dH_post[i] = <dX'_i, y>``, ``dH_res[i, j] = <dX'_i, X_j>``, ``dX_j =
    Σ_i H_res[i, j] dX'_i``."""
    X, y, post, res = kept
    n = X.shape[2]
    G = dout[:, None] if reduce else jnp.swapaxes(dout, 1, 2)
    m = G.shape[1]
    g = lambda i: 0 if reduce else i        # dX'_i's slab; X_j's is m + j
    dXs, dy, dots = _hc_pass(
        path, [G, jnp.swapaxes(X, 1, 2), y[:, None]],
        coef=_hc_write_coef(X, post, res),
        outs=(tuple(tuple((i * n + j, g(i)) for i in range(n))
                    for j in range(n)),
              (tuple((n * n + i, g(i)) for i in range(n)),)),
        dots=tuple((g(i), m + n) for i in range(n))
        + tuple((g(i), m + j) for i in range(n) for j in range(n)))
    dots = _hc_lanes(dots)
    return (jnp.swapaxes(dXs, 1, 2), dy[:, 0], dots[:n],
            dots[n:].reshape(n, n, -1))


_hc_write.defvjp(_hc_write_fwd, _hc_write_bwd)


class HyperConnected(Module):
    """One sub-block ``body`` on a residual path of ``streams`` streams that
    are mixed a token at a time (manifold-constrained hyper-connections,
    arXiv:2512.24880, on hyper-connections, arXiv:2409.19606), in place of
    ``x + body(x)``.  State ``X`` ``(B, T, n, d)``::

        u = (vec(X) / rms(vec(X))) Phi                 Phi (n·d, 2n + n²)
        H_pre  = sigmoid(a_pre · u[:n] + b_pre)                        (n,)
        H_post = 2 sigmoid(a_post · u[n:2n] + b_post)                  (n,)
        H_res  = Sinkhorn(clip(a_res · mat(u[2n:]) + b_res))         (n, n)
        y = body(Σ_i H_pre[i] X[i])
        X'[i] = Σ_j H_res[i, j] X[j] + H_post[i] y

    ``body`` brings its own pre-norm.  Sinkhorn is ``sinkhorn_iters`` times
    columns then rows of ``exp(·)`` divided by their sums + ``hc_eps``, so
    H_res's rows sum to 1 and its columns nearly (``hc_sinkhorn_err``, the
    largest |column sum − 1| of a call, :attr:`SINKHORN_ERR`); the gradient
    goes through every iteration.  ``expand``: the input is ``(B, T, d)``
    and every stream starts as a copy of it (a model's first sub-block);
    ``reduce``: the result is Σ_i X'[i] ``(B, T, d)`` (its last).

    The statistics (rms, u's scaling, the three maps, Sinkhorn) are float32
    whatever the compute dtype, with the tokens on the minor axis: an
    ``(n, n)`` matrix a token would pad 64-fold in the TPU's tiles.

    **How the state is held and moved.**  ``X`` is ``(B, T, n, d)`` to its
    callers and stream-major in memory: every pass takes it as
    ``jnp.swapaxes(X, 1, 2)``, ``(B, n, T, d)``, a stream one ``(T, d)`` slab
    like any activation of the model, and XLA folds the transposes into the
    layout it keeps ``X`` in from sub-block to sub-block (``{3,1,2,0}``: no
    copy, pinned by ``tests/test_tpu_compile.py``).  The mixing is two
    operations with hand-written backwards round the body, each a few
    *passes* over the state (``ops/pallas/hc_mix.py::Pass``: per-token
    multiply-adds of slabs, per-token inner products, the ``Phi`` product),
    run by the kernel ``penroz_hc_mix`` on a TPU where its tiles admit ``(T,
    d)`` and by the same formulation in ``jnp`` anywhere else
    (:func:`_token_mix`; :meth:`mix_plan` says which, the ``hc_mix plan:``
    line).  A pass reads the streams in their own type, widens them in
    registers, sums in float32 and writes the model's type: no float32
    array of the state's size exists, and ``X`` is never laid out as ``(B·T,
    n·d)`` (the ``Phi`` product is ``n`` products of a slab with its ``d``
    columns of ``Phi``, float32 accumulation).  In units of one stream's
    ``(T, d)``, reads + writes:

    - :func:`_hc_read`: the statistics (sum of squares and ``Phi`` product;
      n + 0), then ``x_in`` (n + 1);
    - :func:`_hc_write`: ``X'`` (n + 1 reads, n writes);
    - the backward of :func:`_hc_write`: one pass over ``dX'``, ``X`` and
      ``y`` (2n + 1 reads) gives ``dy``, ``Σ_i H_res[i, j] dX'_i`` (n + 1
      writes) and the inner products ``<dX'_i, y>``, ``<dX'_i, X_j>``;
    - the backward of :func:`_hc_read`: ``<dx_in, X_i>`` (n + 1 reads); the
      maps' own backward through every Sinkhorn iteration, on 24 numbers a
      token, which stays XLA's as :func:`_hc_maps` is (the pull-back the
      forward made); then ``dX`` whole, summed in float32 and rounded once
      (2n + 1 reads, n writes: ``dx_in``, ``X``, the writing half's part,
      which reaches it as the cotangent of the ``X`` that :func:`_hc_read`
      hands on, and ``dP Phi_j`` on the MXU), with ``dPhi_j = dP X_j``
      summed over the tokens in the same pass.

    In training a sub-block runs under ``jax.checkpoint``
    (:func:`_recomputed`, as :class:`Looped`'s applications do): ``n``
    streams hold ``n`` times a plain stack's residual; the backward keeps a
    sub-block's input ``X`` and what the kernels wrote and name, and runs
    the rest again.  A property of the container, not an option.  What that
    recomputes of the mixing: the sub-block's forward runs again but for the
    ``X'`` pass (its result is not needed: the operations keep their inputs,
    ``H_pre`` and the maps' pull-back), so the maps are made twice a
    sub-block and the state is passed over 3 + 2 + 3 times, where the
    formulas written out plainly and left to autodiff moved twelve states'
    worth of bytes forward alone (PERF.md §6, PR 48)."""

    def __init__(self, features: int, body: Module, streams: int = 4,
                 sinkhorn_iters: int = 20, hc_eps: float = 1e-6,
                 res_clamp: Sequence[float] = (-30.0, 30.0),
                 eps: float = 1e-6, expand: bool = False,
                 reduce: bool = False):
        if int(streams) < 1 or int(sinkhorn_iters) < 0:
            raise ValueError("hyperconnected needs streams >= 1 and "
                             "sinkhorn_iters >= 0")
        self.features, self.body = int(features), body
        self.streams, self.sinkhorn_iters = int(streams), int(sinkhorn_iters)
        self.hc_eps, self.eps = float(hc_eps), float(eps)
        self.res_clamp = (float(res_clamp[0]), float(res_clamp[1]))
        self.expand, self.reduce = bool(expand), bool(reduce)

    def children(self):
        return [("body", self.body)]

    # |column sum - 1| of the mixing matrix after its last iteration
    SINKHORN_ERR = Stat("hc_sinkhorn_err", "max", "hc")

    def stats(self):
        return (self.SINKHORN_ERR,)

    @property
    def maps(self) -> int:
        return 2 * self.streams + self.streams ** 2

    def param_shapes(self):
        return {"phi.weight": (self.maps, self.streams * self.features),
                "alpha": (3,), "bias": (self.maps,)}

    def init(self, rng):
        """Phi N(0, 0.02); the dynamic part enters at ``alpha`` 0.01, so
        that at the start the maps are their static parts: H_pre 1/2,
        H_post 1, H_res Sinkhorn of 4·I (near the identity)."""
        n = self.streams
        bias = jnp.concatenate([jnp.zeros((2 * n,), jnp.float32),
                                4.0 * jnp.eye(n).reshape(-1)])
        return {self.key("phi.weight"): 0.02 * jax.random.normal(
                    rng, self.param_shapes()["phi.weight"], jnp.float32),
                self.key("alpha"): jnp.full((3,), 0.01, jnp.float32),
                self.key("bias"): bias}

    def mix_plan(self, batch: int, seq: int, on_tpu: bool,
                 itemsize: int) -> dict:
        """How a call's passes over the state run: ``path`` ``kernel``
        (``ops/pallas/hc_mix.py``, on one TPU (:func:`_one_tpu`) where its
        tiles admit ``(seq, features)``) or ``fused`` (the same passes for
        XLA to fuse and, under a mesh, to partition), and the
        ``bytes`` a forward call of a sub-block moves of streams, mixed
        input and ``y``: (4 · streams + 2) token vectors."""
        from penroz_tpu.ops.pallas import hc_mix
        kernel = on_tpu and hc_mix.fits(seq, self.features, self.streams,
                                        itemsize)
        tokens = batch * seq
        return {"path": "kernel" if kernel else "fused",
                "streams": self.streams, "features": self.features,
                "tokens": tokens, "bytes": (4 * self.streams + 2) * tokens
                * self.features * itemsize}

    def _cfg(self, X, ctx) -> _HcMaps:
        plan = self.mix_plan(*X.shape[:2], _one_tpu(X, ctx.platform),
                             X.dtype.itemsize)
        return _HcMaps(self.streams, self.features, self.sinkhorn_iters,
                       self.eps, self.hc_eps, self.res_clamp, plan["path"])

    def _own(self, ctx):
        return (self._p(ctx, "phi.weight"),
                ctx.params[self.key("alpha")].astype(jnp.float32),
                ctx.params[self.key("bias")].astype(jnp.float32))

    def maps_of(self, X, ctx):
        """``(H_pre (n, tokens), H_post (n, tokens), H_res (n, n, tokens))``
        in float32 of ``X`` ``(B, T, n, d)``."""
        cfg = self._cfg(X, ctx)
        phi, alpha, bias = self._own(ctx)
        return _hc_maps(cfg, *_hc_stats(cfg.path, jnp.swapaxes(X, 1, 2), phi),
                        alpha, bias)

    def _mix(self, X, ctx):
        cfg = self._cfg(X, ctx)
        x_in, post, res, X = _hc_read(cfg, X, *self._own(ctx))
        ctx.report(self.SINKHORN_ERR,
                   jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0)))
        return _hc_write(cfg.path, self.reduce, X, self.body.apply(x_in, ctx),
                         post, res)

    def _apply(self, x, ctx):
        n = self.streams
        if self.expand:
            x = jnp.broadcast_to(x[:, :, None, :],
                                 x.shape[:2] + (n, x.shape[-1]))
        if x.ndim != 4 or x.shape[2] != n or x.shape[3] != self.features:
            raise ValueError(
                f"hyperconnected takes (B, T, {n}, {self.features}) streams "
                f"(or (B, T, {self.features}) with expand), got {x.shape}")
        return self._mix(x, ctx)

    def apply(self, x, ctx):
        return _recomputed(lambda inner, h: self._apply(h, inner), ctx,
                           [self], x)


def _kept_names() -> tuple:
    """What a recomputation (:func:`_recomputed`) keeps: the outputs the
    kernels' callers name (``checkpoint_name``)."""
    from penroz_tpu.ops import losses
    from penroz_tpu.ops.pallas import flash_attention as fa
    return fa.OUT_NAME, fa.LSE_NAME, losses.LSE_NAME


def _recomputed(fn, ctx, mods, *args):
    """``fn(ctx, *args)``; in training under ``jax.checkpoint``, as a
    function of ``mods``' own parameters and ``args``: the backward keeps
    ``args`` and what the kernels' callers name (:func:`_kept_names`) and
    runs the rest of the inside again.  What the inside leaves on its
    context (auxiliary losses, buffer updates, what its modules reported,
    the dropout counter) is handed on to ``ctx``."""
    if not ctx.training:
        return fn(ctx, *args)
    prefixes = tuple(m.prefix + "." for m in mods)
    own = {k: v for k, v in ctx.params.items() if k.startswith(prefixes)}
    left = {}   # what the inside leaves that is no array

    def pure(params, rng, *inputs):
        inner = copy.copy(ctx)
        inner.params, inner.rng = params, rng
        inner.aux_losses, inner.buffer_updates, inner.stats = [], {}, {}
        out = fn(inner, *inputs)
        left.update(rng_counter=inner._rng_counter, stats=inner.stats)
        return (out, inner.aux_losses, inner.buffer_updates,
                {name: value for name, (_, value, _) in inner.stats.items()})

    keep = jax.checkpoint_policies.save_only_these_names(*_kept_names())
    out, aux, updates, reported = jax.checkpoint(pure, policy=keep)(
        own, ctx.rng, *args)
    ctx._rng_counter = left["rng_counter"]
    ctx.aux_losses.extend(aux)
    ctx.buffer_updates.update(updates)
    for name, value in reported.items():
        stat, _, reports = left["stats"][name]
        ctx.report(stat, value, reports)
    return out


class Looped(Module):
    """One stack of blocks run ``steps`` times with the same weights, an
    exit after every pass (Zhu et al. 2025, "Scaling Latent Reasoning via
    Looped Language Models")::

        u = x
        for t in 1..steps:
            for block in body: u = block(u)       # the SAME blocks each pass
            h_t = norm(u);  u = h_t               # the final norm is inside
            z_t = head(h_t);  g_t = gate(h_t)     # logits; one scalar a token

    The container owns one set of parameters (``body.<i>…``, ``norm``,
    ``head``, ``gate``); a shared weight's gradient is the sum over its
    ``steps`` applications.  :meth:`apply` returns the last pass's logits
    (generation never leaves early), so the container stands where a plain
    stack has its blocks, final norm and head.  Given ``ctx.targets`` it
    also leaves in ``ctx.exits`` every exit's per-token cross-entropy and
    gate logit, ``(steps, …)`` each, from which the model takes
    ``ops/losses.py::expected_exit_loss`` (weight ``entropy_weight``).

    In training every application of a block runs under ``jax.checkpoint``
    (a pass's last block together with the final norm and the exit: head,
    gate, cross-entropy): the backward keeps each application's input and
    recomputes its inside, so ``steps`` passes hold ``steps × len(body)``
    block inputs and not ``steps`` times a plain stack's activations, nor
    ``steps`` sets of logits.  Of the inside it keeps what the kernels
    wrote and name (:func:`_kept_names`: the flash forward's ``o`` and
    logsumexp, the cross-entropy forward's logsumexp), so the recomputation
    runs the matmuls, norms and RoPE again and neither kernel's forward.
    That is a property of the container, not an option.  With a KV cache
    each (pass, layer) has its own slot: pass ``t`` offsets its attention
    layers' slots by ``t · slots_per_pass`` (``Ctx.layer_offset``; the
    model builder counts the slots).
    """

    def __init__(self, steps: int, body: Sequence[Module], norm: Module,
                 head: Module, gate: Module, entropy_weight: float = 0.1):
        if int(steps) < 1:
            raise ValueError(f"looped steps must be >= 1, got {steps}")
        if not body:
            raise ValueError("looped body must hold at least one block")
        self.steps = int(steps)
        self.body = list(body)
        self.norm, self.head, self.gate = norm, head, gate
        self.entropy_weight = float(entropy_weight)
        self.slots_per_pass = 0  # attention layers a pass; the model builder

    def children(self):
        return ([(f"body.{i}", b) for i, b in enumerate(self.body)]
                + [("norm", self.norm), ("head", self.head),
                   ("gate", self.gate)])

    def stats(self):
        """Each pass's mean cross-entropy and the mean exit distribution:
        the model works them out with its loss over the exits
        (``ops/losses.py::expected_exit_loss``) and reports them."""
        return tuple(Stat(name, "mean", name, shape=(self.steps,))
                     for name in ("pass_loss", "exit_mass"))

    def plan(self, training: bool) -> dict:
        """The loop's counters.  ``kept_outputs``: the names the
        recomputation's policy saves, kept wherever a kernel's caller gave
        them (a path that names nothing, the jnp attention, keeps nothing:
        what that costs in HBM is the device's to say, ``hbm_peak_gb``)."""
        applications = self.steps * len(self.body)
        return {"steps": self.steps, "layers": len(self.body),
                "applications": applications,
                "recomputed_applications": applications if training else 0,
                "cache_slots": self.steps * self.slots_per_pass,
                "kept_outputs": ",".join(_kept_names()) if training else ""}

    def _exit(self, ctx, h, targets):
        """One exit: (per-token cross-entropy, gate logit), fp32."""
        from penroz_tpu.ops import losses
        rows = losses.fused_cross_entropy_rows(
            self.head.apply(h, ctx), targets, platform=ctx.platform)
        return rows, self.gate.apply(h, ctx)[..., 0].astype(jnp.float32)

    def _pass_end(self, ctx, u, targets):
        """The last block of a pass, the final norm and, given targets, the
        exit: ``(h_t, exit or None)``.  One unit under recomputation, so
        that exit ``t``'s backward waits for ``h_t``'s cotangent from pass
        ``t + 1`` and the exits' logits are never held side by side."""
        h = self.norm.apply(self.body[-1].apply(u, ctx), ctx)
        return h, (self._exit(ctx, h, targets) if targets is not None
                   else None)

    def apply(self, x, ctx):
        _record_plan("loop", **self.plan(ctx.training))
        u, exits = x, []
        for t in range(self.steps):
            ctx.layer_offset = t * self.slots_per_pass
            for block in self.body[:-1]:
                u = _recomputed(lambda inner, h, b=block: b.apply(h, inner),
                                ctx, [block], u)
            u, out = _recomputed(
                self._pass_end, ctx,
                [self.body[-1], self.norm, self.head, self.gate], u,
                ctx.targets)
            exits.append(out)
        ctx.layer_offset = 0
        if ctx.targets is not None:
            ctx.exits = tuple(jnp.stack(part) for part in zip(*exits))
        return self.head.apply(u, ctx)


class GatedMLP(Module):
    """SwiGLU/GeGLU gated MLP (Gemma/LLaMA style)."""

    def __init__(self, in_features: int, intermediate_size: int,
                 bias: bool = False, activation: str = "gelu_pytorch_tanh"):
        self.gate_proj = Linear(in_features, intermediate_size, bias=bias)
        self.up_proj = Linear(in_features, intermediate_size, bias=bias)
        self.down_proj = Linear(intermediate_size, in_features, bias=bias)
        self.activation = activation

    def children(self):
        return [("gate_proj", self.gate_proj), ("up_proj", self.up_proj),
                ("down_proj", self.down_proj)]

    def _act(self, x):
        return _gated_activation(self.activation, x)

    def apply(self, x, ctx):
        gated = self._act(self.gate_proj.apply(x, ctx)) * self.up_proj.apply(x, ctx)
        return self.down_proj.apply(gated, ctx)


def _gated_activation(name: str, x):
    """silu / gelu / gelu_pytorch_tanh dispatch shared by the gated MLPs."""
    if name in ("silu", "swish"):
        return jax.nn.silu(x)
    return jax.nn.gelu(x, approximate=(name == "gelu_pytorch_tanh"))


# An expert that is not gated: ``relu(x W_up)² W_down``, two matrices and no
# gate projection (``mlp_hidden_act: relu2``).
UNGATED = "relu2"


def _expert_hidden(activation: str, up, gate=None):
    """An expert's hidden row from its projections: ``act(gate) · up``, or
    ``relu(up)²`` for the expert that has no gate (:data:`UNGATED`)."""
    if activation == UNGATED:
        return jnp.square(jax.nn.relu(up))
    return _gated_activation(activation, gate) * up


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(probs, k: int):
    """``jax.lax.top_k`` whose gradient is a compare: ``dprobs[..., e] = Σ_j
    [e = index[..., j]] · dvalues[..., j]``, a fused compare-select-sum in
    place of the scatter XLA makes of it (an element at a time: 1.0 ms for
    8 192 x 10 into 256 on a v5e against 0.16, PERF.md §6)."""
    return tuple(jax.lax.top_k(probs, k))


def _top_k_fwd(probs, k):
    values, index = jax.lax.top_k(probs, k)
    return (values, index), (index, probs.shape[-1])


def _top_k_bwd(k, kept, cotangents):
    (index, width), (dvalues, _) = kept, cotangents  # indices: no derivative
    hit = index[..., None] == jnp.arange(width, dtype=index.dtype)
    return (jnp.sum(jnp.where(hit, dvalues[..., None], 0), axis=-2),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


class _DroplessConfig(NamedTuple):
    row_tile: int
    rows: int           # rows a round is handed
    held: int           # experts held: a tile of expert ``held`` is empty
    activation: str
    on_tpu: bool
    combine: str        # how rows come back to their tokens: runs | take


class _DroplessLayout(NamedTuple):
    """Where the (token, choice) pairs of the held experts lie: sorted by
    expert, each expert's group padded to whole tiles, and inside a group
    ascending in the token (a token meets an expert at most once), so token
    ``n``'s row in expert ``e`` is ``starts[e] + pos[e, n] - 1``.  Integers
    only: nothing here has a derivative."""
    chosen: jax.Array       # (held, tokens) bool: the router sent n to e
    pos: jax.Array          # (held, tokens): e's chosen tokens up to n's
    sizes: jax.Array        # (held,) rows of each group
    starts: jax.Array       # (held,) a group's first row
    padded: jax.Array       # () rows of all groups, each in whole tiles
    tile_group: jax.Array   # (rows_bound // row_tile,) a tile's expert
    place_row: jax.Array    # (places, tokens) a token's rows, -1 for none


class _RoundRows(NamedTuple):
    """One round's rows (``cfg.rows`` of the layout)."""
    tok: jax.Array          # the token a row reads; 0 where it is not live
    weight: jax.Array       # float32; 0 where it is not live
    groups: jax.Array       # (rows // row_tile,) its tiles' experts
    live: jax.Array         # a real row in a tile the products compute
    first: jax.Array        # the round's first row in the layout
    rank: jax.Array         # (tiles, row_tile) a row's place in its group


def _held_choices(top_vals, top_idx, first: int, held: int):
    """The ``(tokens, top_k)`` choices reduced to the held experts by dense
    compares, tokens on the lanes: ``chosen[e, n]`` and ``weight[e, n] =
    Σ_k top_vals[n, k] · [top_idx[n, k] = first + e]`` (float32), and the
    compare itself ``(top_k, held, tokens)``.  The weight's derivative in
    ``top_vals`` is a broadcast and a mask."""
    hit = (top_idx.T[:, None, :] - first
           == jnp.arange(held, dtype=top_idx.dtype)[None, :, None])
    weight = jnp.sum(jnp.where(
        hit, top_vals.T[:, None, :].astype(jnp.float32), 0.0), axis=0)
    return hit, jnp.any(hit, axis=0), weight


def _dropless_layout(hit, chosen, tile: int, bound: int) -> _DroplessLayout:
    """The stable counting sort as one cumulative sum along the tokens."""
    held = chosen.shape[0]
    pos = jnp.cumsum(chosen.astype(jnp.int32), axis=1)
    sizes = pos[:, -1]
    padded = -(-sizes // tile) * tile
    ends = jnp.cumsum(padded)
    starts = ends - padded
    tile_start = jnp.arange(bound // tile, dtype=jnp.int32) * tile
    # the expert a tile belongs to; ``held`` past the last real row
    tile_group = jnp.searchsorted(ends, tile_start, side="right").astype(
        jnp.int32)
    row = starts[:, None] + pos - 1
    if held <= hit.shape[0]:        # a place an expert held
        place_row = jnp.where(chosen, row, -1)
    else:                           # a place a choice
        place_row = jnp.sum(jnp.where(hit, row[None] + 1, 0), axis=1) - 1
    return _DroplessLayout(chosen, pos, sizes, starts, ends[-1], tile_group,
                           place_row)


def _round_rows(cfg, j, layout, weight) -> _RoundRows:
    """Round ``j``'s rows.  The one inverse of the layout is made here, for
    the round's rows only and without an indexed write: a row of rank ``i``
    in its group reads the token at which the group's running count first
    passes ``i``, which is how many tokens' counts do not (the counts
    ascend), a dense compare and a sum along the lanes."""
    tiles = cfg.rows // cfg.row_tile
    first = j * cfg.rows
    groups = jax.lax.dynamic_slice_in_dim(layout.tile_group, j * tiles, tiles)
    held = jnp.minimum(groups, cfg.held - 1)
    rank = (first + jnp.arange(cfg.rows, dtype=jnp.int32).reshape(
        tiles, cfg.row_tile)) - layout.starts[held][:, None]
    live = ((groups < cfg.held)[:, None]
            & (rank < layout.sizes[held][:, None])).reshape(cfg.rows)
    tok = jnp.sum(layout.pos[held][:, None, :] <= rank[:, :, None], axis=-1,
                  dtype=jnp.int32).reshape(cfg.rows)
    tok = jnp.where(live, tok, 0)
    row_weight = jnp.where(
        live, weight[jnp.repeat(held, cfg.row_tile), tok], 0.0)
    return _RoundRows(tok, row_weight, groups, live, first, rank)


def _round_out(cfg, xs, w_gate, w_up, w_down, tile_group):
    """One round's rows through their experts: ``(rows, d)``.  ``w_gate``
    is ``None`` for experts that are not gated: two products, not three."""
    from penroz_tpu.ops.pallas import moe_gmm
    product = functools.partial(moe_gmm.grouped_matmul, tile_group=tile_group,
                                row_tile=cfg.row_tile, on_tpu=cfg.on_tpu)
    hidden = _expert_hidden(
        cfg.activation, product(xs, w_up),
        None if w_gate is None else product(xs, w_gate))
    return product(hidden, w_down)


def _token_runs(cfg, layout, rnd, tokens: int):
    """``(first, one past the last)`` row of the round that each held expert
    has for each tile of tokens, ``(held, token tiles)`` each: inside a
    group the rows ascend in their token, so a tile's are one run, from the
    group's count before the tile to its count at the tile's end."""
    from penroz_tpu.ops.pallas import moe_combine
    tile = moe_combine.token_tile(tokens)
    ahead = jnp.pad(layout.pos[:, tile - 1::tile], ((0, 0), (1, 0)))
    run = jnp.clip(layout.starts[:, None] + ahead - rnd.first, 0, cfg.rows)
    return run[:, :-1], run[:, 1:]


def _rows_to_tokens(cfg, layout, rnd, rows, scale, y):
    """``y[n] + Σ_{live rows r of token n} scale[r] · rows[r]``, float32, by
    reads: on the TPU each token tile reads every group's contiguous run of
    its rows once (``ops/pallas/moe_combine.py``); elsewhere a token reads
    the row it has in each of its places, a row of zeros where it has none
    there or the row lies in another round."""
    if cfg.combine == "runs":
        from penroz_tpu.ops.pallas import moe_combine
        return moe_combine.rows_to_tokens(
            rows, scale, rnd.tok, *_token_runs(cfg, layout, rnd, y.shape[0]),
            y, places=layout.place_row.shape[0])
    local = layout.place_row - rnd.first
    here = (layout.place_row >= 0) & (local >= 0) & (local < cfg.rows)
    local = jnp.where(here, local, 0)
    got = (jnp.take(rows, local, axis=0).astype(jnp.float32)
           * jnp.where(here, jnp.take(scale, local), 0.0)[:, :, None])
    return y + jnp.sum(got, axis=0)


def _rows_to_choices(cfg, layout, rnd, values):
    """A scalar a row, back at its ``(expert, token)``: ``(held, tokens)``,
    0 where the round holds no row.  Dense again: a tile's rows against the
    running count of the tile's expert, then the tiles of an expert
    summed."""
    tiles = cfg.rows // cfg.row_tile
    held = jnp.minimum(rnd.groups, cfg.held - 1)
    values = jnp.where(rnd.live, values, 0.0).reshape(tiles, cfg.row_tile)
    hit = layout.pos[held][:, None, :] == rnd.rank[:, :, None] + 1
    per_tile = jnp.where(
        layout.chosen[held],
        jnp.sum(jnp.where(hit, values[:, :, None], 0.0), axis=1), 0.0)
    mine = held[None, :] == jnp.arange(cfg.held, dtype=held.dtype)[:, None]
    return jnp.sum(jnp.where(mine[:, :, None], per_tile[None], 0.0), axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dropless_rows(cfg, x, weight, w_gate, w_up, w_down, layout, rounds):
    """``(y, placed)``: ``y[n] = Σ_{held experts e of token n} weight[e, n] ·
    expert_e(x[n])`` over the first ``rounds`` rounds of the sorted rows (a
    traced count: the loop runs as long as there are rows, not as long as
    the bound), and how many real rows those rounds handed to a live tile
    of the products, counted as the rounds run.

    Its derivative is taken a round at a time too, each round recomputed
    from ``x``, the layout and the weights: nothing of a round outlives it
    in either direction, so what the layer keeps for the backward is what
    it was given."""
    def one(j, carry):
        y, placed = carry
        rnd = _round_rows(cfg, j, layout, weight)
        out = _round_out(cfg, x[rnd.tok], w_gate, w_up, w_down, rnd.groups)
        return (_rows_to_tokens(cfg, layout, rnd, out, rnd.weight, y),
                placed + jnp.sum(rnd.live, dtype=jnp.int32))

    y, placed = jax.lax.fori_loop(
        0, rounds, one, (jnp.zeros(x.shape, jnp.float32), jnp.int32(0)))
    return y.astype(x.dtype), placed.astype(jnp.float32)


def _dropless_rows_fwd(cfg, x, weight, w_gate, w_up, w_down, layout, rounds):
    kept = (x, weight, w_gate, w_up, w_down, layout, rounds)
    return _dropless_rows(cfg, *kept), kept


def _dropless_rows_bwd(cfg, kept, cotangents):
    x, weight, w_gate, w_up, w_down, layout, rounds = kept
    dy, _ = cotangents                   # the count has no derivative
    gated = w_gate is not None
    stacks = (w_gate, w_up, w_down) if gated else (w_up, w_down)
    whole = (lambda s: s) if gated else (lambda s: (None, *s))

    def round_grads(j, dx, dweight):
        """Round ``j`` recomputed and pulled back: its rows' gradients added
        into ``dx`` and ``dweight``, and the stacks' gradients it made."""
        rnd = _round_rows(cfg, j, layout, weight)
        _, pull = jax.vjp(
            lambda xs, w, *s: (_round_out(cfg, xs, *whole(s),
                                          rnd.groups).astype(
                jnp.float32) * w[:, None]),
            x[rnd.tok], rnd.weight, *stacks)
        dxs, dw, *ds = pull(dy[rnd.tok].astype(jnp.float32))
        return (_rows_to_tokens(cfg, layout, rnd, dxs,
                                rnd.live.astype(jnp.float32), dx),
                dweight + _rows_to_choices(cfg, layout, rnd, dw), ds)

    zeros = (jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(weight))

    def one_round():
        # the usual case (the buffer is a row a token): the stacks'
        # gradients leave as the products made them
        dx, dweight, ds = round_grads(0, *zeros)
        return dx, dweight, tuple(ds)

    def many_rounds():
        # summed over the rounds in float32, as one product over all the
        # rows would accumulate them, and cast once
        def one(j, grads):
            dx, dweight, ds = round_grads(j, *grads[:2])
            return dx, dweight, tuple(a + g.astype(jnp.float32)
                                      for a, g in zip(grads[2], ds))

        sums = tuple(jnp.zeros(w.shape, jnp.float32) for w in stacks)
        dx, dweight, sums = jax.lax.fori_loop(0, rounds, one, (*zeros, sums))
        return dx, dweight, tuple(g.astype(w.dtype)
                                  for g, w in zip(sums, stacks))

    # no round at all (nothing routed here) takes the first branch too: its
    # one round finds padding rows and empty tiles only
    dx, dweight, dstacks = jax.lax.cond(rounds <= 1, one_round, many_rounds)
    return (dx.astype(x.dtype), dweight, *whole(dstacks), None, None)


_dropless_rows.defvjp(_dropless_rows_fwd, _dropless_rows_bwd)


class MixtureOfExperts(Module):
    """Top-k routed mixture of gated-MLP experts (Mixtral/Switch style).

    TPU-first layout: expert weights are *stacked* on a leading expert
    dimension — ``experts.gate_proj.weight`` (E, H, D) etc. — so a single
    einsum drives the MXU for every expert at once, and the expert dimension
    shards over the mesh ``expert`` axis (expert parallelism: each device
    computes its expert shard for all tokens; the top-k-weighted combine is
    a contraction over E, which XLA turns into a psum over the axis).

    Two dispatch modes (``dispatch`` DSL arg):

    - ``"dense"`` (default): every expert processes every token and
      non-selected contributions are zeroed by the router weights.  No
      token dropping, exact top-k math, at the cost of E/top_k× extra MLP
      FLOPs — the right trade below ~16 experts.
    - ``"capacity"`` (Switch/Mesh-TF style): the flattened batch splits
      into fixed-size groups of ``DISPATCH_GROUP`` tokens (padded up with
      masked rows when not divisible) and each group packs its tokens
      into per-expert buffers of static capacity
      ``C = ceil(top_k · DISPATCH_GROUP / E · capacity_factor)`` via
      one-hot dispatch einsums; each expert computes only its (C, d)
      buffer per group and a combine einsum scatters results back.  MLP
      FLOPs drop by ~E/top_k× (the point of sparse MoE); tokens routed
      past their group's per-expert capacity lose that expert's
      contribution (Switch token dropping, applied per group — uneven
      routing across groups can drop tokens a single global buffer would
      have served).  All shapes stay static for XLA, and the buffers
      shard on the mesh ``expert`` axis like the stacked weights.

    No reference equivalent (the reference has no MoE; nearest is GatedMLP,
    neural_net_layers.py:158-174) — this is a capability extension wired
    into the same DSL registry.
    """

    def __init__(self, in_features: int, intermediate_size: int,
                 num_experts: int, top_k: int = 2, bias: bool = False,
                 activation: str = "silu", aux_loss_coef: float = 0.0,
                 dispatch: str = "dense", capacity_factor: float = 1.25,
                 norm_topk: bool = True, shared_expert_size: int = 0,
                 experts_held: Optional[int] = None, first_expert: int = 0,
                 routed_scale: float = 1.0, shared_expert_gate: bool = True,
                 scoring: str = "softmax", selection_bias: bool = False,
                 selection_bias_init: Optional[Sequence[float]] = None,
                 bias_update_rate: float = 0.001, latent: int = 0):
        # ``latent`` (LatentMoE): the routed experts live at that width and
        # the layer owns the way there and back, ``latent_down.weight``
        # (latent, d) before them and ``latent_up.weight`` (d, latent)
        # after their weighted sum; the router and the shared expert stay
        # at the full width.  ``activation="relu2"``: experts (the shared
        # one too) that are not gated, ``relu(x W_up)² W_down``: two stacks
        # and no ``gate_proj``.
        self.latent = int(latent)
        if self.latent < 0:
            raise ValueError(f"latent must be a width or 0, got {latent}")
        if activation == UNGATED and dispatch == "capacity":
            raise ValueError("dispatch 'capacity' computes gated experts; "
                             "'relu2' takes 'dense' or 'dropless'")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring must be 'softmax' or 'sigmoid', "
                             f"got {scoring!r}")
        # ``scoring="sigmoid"``: an expert's score is sigmoid(logit), each
        # on its own (DeepSeek-V3's router).  ``selection_bias``: a buffer
        # b (num_experts,) is added to the scores for the *choice* of the
        # top-k alone; the weights are the scores of the chosen, without
        # it.  No gradient reaches b: once an optimizer step it moves by
        # ``bias_update_rate`` towards balance, b += rate · sign(mean load
        # - load) over the step's tokens (loss-free balancing,
        # arXiv:2408.15664).  ``selection_bias_init``: its first value
        # (default zeros).
        self.scoring = scoring
        self.selection_bias = bool(selection_bias)
        self.bias_update_rate = float(bias_update_rate)
        if selection_bias_init is not None and (
                not selection_bias
                or len(selection_bias_init) != num_experts):
            raise ValueError("selection_bias_init gives one value an expert "
                             "of a router with selection_bias")
        self.selection_bias_init = (
            None if selection_bias_init is None
            else tuple(float(b) for b in selection_bias_init))
        if top_k < 1 or top_k > num_experts:
            raise ValueError(f"top_k={top_k} outside [1, {num_experts}]")
        if bias:
            raise ValueError("MixtureOfExperts does not support bias yet")
        if dispatch not in ("dense", "capacity", "dropless"):
            raise ValueError(f"dispatch must be 'dense', 'capacity' or "
                             f"'dropless', got {dispatch!r}")
        # One rank's share of an expert-parallel layer: the router scores
        # all ``num_experts``, this module holds (and computes) experts
        # ``first_expert .. first_expert + experts_held - 1`` only; what the
        # others would have added is left out (the partial sum an
        # expert-parallel rank holds before the exchange).
        held = num_experts if experts_held is None else int(experts_held)
        if not (1 <= held <= num_experts
                and 0 <= int(first_expert) <= num_experts - held):
            raise ValueError(
                f"experts_held={experts_held} from first_expert="
                f"{first_expert} does not lie within {num_experts} experts")
        if dispatch == "capacity" and held != num_experts:
            raise ValueError("dispatch 'capacity' holds every expert; a "
                             "share takes 'dense' or 'dropless'")
        self.experts_held, self.first_expert = held, int(first_expert)
        # DeepSeek-style ``routed_scaling_factor``: the (renormalised)
        # top-k weights times a constant.
        self.routed_scale = float(routed_scale)
        # ``False``: the shared expert is added as it is (no sigmoid gate).
        self.shared_expert_gate = bool(shared_expert_gate)
        if float(capacity_factor) <= 0.0:
            raise ValueError(f"capacity_factor must be > 0, "
                             f"got {capacity_factor}")
        self.dispatch = dispatch
        self.capacity_factor = float(capacity_factor)
        # Qwen2-MoE options: ``norm_topk=False`` keeps the raw softmax
        # mass on the selected experts (HF ``norm_topk_prob`` default);
        # ``shared_expert_size`` adds an always-on gated-MLP expert whose
        # contribution is scaled by a sigmoid token gate and SUMMED with
        # the routed output (Qwen2MoeSparseMoeBlock.shared_expert).
        self.norm_topk = bool(norm_topk)
        self.shared_expert_size = int(shared_expert_size)
        self.in_features = int(in_features)
        self.intermediate_size = int(intermediate_size)
        self.num_experts = int(num_experts)
        self.top_k = int(top_k)
        self.activation = activation
        # Switch/Mixtral-style load-balance loss weight; 0 disables.  A
        # top-k router trained purely on task loss commonly collapses onto
        # few experts, and dense dispatch makes the collapse invisible (no
        # capacity-overflow signal) — the aux term and the router_fraction
        # buffer below are the countermeasure + the observability.
        self.aux_loss_coef = float(aux_loss_coef)

    def param_shapes(self):
        d, h, e = self.in_features, self.intermediate_size, self.num_experts
        held, w = self.experts_held, self.latent or self.in_features
        shapes = {
            "router.weight": (e, d),
            "experts.gate_proj.weight": (held, h, w),
            "experts.up_proj.weight": (held, h, w),
            "experts.down_proj.weight": (held, w, h),
        }
        if self.latent:
            shapes.update({"latent_down.weight": (w, d),
                           "latent_up.weight": (d, w)})
        if self.shared_expert_size:
            hs = self.shared_expert_size
            shapes.update({
                "shared_expert.gate_proj.weight": (hs, d),
                "shared_expert.up_proj.weight": (hs, d),
                "shared_expert.down_proj.weight": (d, hs),
            })
            if self.shared_expert_gate:
                shapes["shared_expert_gate.weight"] = (1, d)
        if self.activation == UNGATED:
            shapes = {name: shape for name, shape in shapes.items()
                      if "gate_proj" not in name}
        return shapes

    def init(self, rng):
        # torch-Linear-style U(-1/sqrt(fan_in), ·) per leaf; fan_in is the
        # trailing (contraction) dim for every weight in this module.
        shapes = self.param_shapes()
        keys = jax.random.split(rng, len(shapes))
        return {self.key(name): _uniform(k, shape,
                                         1.0 / math.sqrt(shape[-1]))
                for k, (name, shape) in zip(keys, shapes.items())}

    def _act(self, x):
        return _gated_activation(self.activation, x)

    def init_buffers(self):
        # Latest per-expert routing fraction (observability; updated each
        # training step like BatchNorm running stats).
        zeros = jnp.zeros((self.num_experts,), jnp.float32)
        buffers = {self.key("router_fraction"): zeros}
        if self.selection_bias:
            # the bias, and the (token, choice) pairs each expert got in
            # the optimizer step so far
            buffers[self.key("selection_bias")] = (
                zeros if self.selection_bias_init is None
                else jnp.asarray(self.selection_bias_init, jnp.float32))
            buffers[self.key("selection_load")] = zeros
        return buffers

    def end_step(self, buffers):
        if not self.selection_bias:
            return {}
        load = buffers[self.key("selection_load")]
        bias = buffers[self.key("selection_bias")]
        return {self.key("selection_bias"): bias + self.bias_update_rate
                * jnp.sign(jnp.mean(load) - load),
                self.key("selection_load"): jnp.zeros_like(load)}

    # What a dropless layer counts of a call: pairs routed to held experts,
    # rows the grouped products computed (the groups padded to whole tiles),
    # the fullest held expert's rows (the per-layer maxima summed), pairs
    # that found no row.
    ROUTED = tuple(Stat(name, "sum", "moe", host=int) for name in (
        "moe_rows", "moe_rows_padded", "moe_load_max", "moe_dropped"))
    BIAS_ABSMAX = Stat("moe_bias_absmax", "max", "hc")   # selection bias

    def stats(self):
        return ((self.ROUTED if self.dispatch == "dropless" else ())
                + ((self.BIAS_ABSMAX,) if self.selection_bias else ()))

    def router_weights(self, x, ctx):
        """(B, T, held) combine weights of the experts held: scores over
        all → top-k → renormalize → scale."""
        top_vals, top_idx = self.route(x, ctx)
        one_hot = jax.nn.one_hot(top_idx, self.num_experts,
                                 dtype=jnp.float32)  # (B, T, k, E)
        weights = jnp.einsum("btk,btke->bte", top_vals, one_hot)
        first = self.first_expert
        return weights[..., first:first + self.experts_held]

    def route(self, x, ctx):
        """``(weights, experts)``, both (B, T, top_k): scores over all
        ``num_experts`` (``scoring``: softmax, or a sigmoid an expert) →
        top-k, of the scores or with ``selection_bias`` of scores + bias
        (the weights stay the scores of the chosen) → renormalize
        (``norm_topk``) → times ``routed_scale``.

        Routing runs entirely in fp32 — logits einsum included: bf16
        rounding before the (monotonic) softmax still flips expert choices
        on near-tie tokens."""
        router = ctx.params[self.key("router.weight")]
        logits = jnp.einsum("btd,ed->bte", x.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = (jax.nn.sigmoid(logits) if self.scoring == "sigmoid"
                 else jax.nn.softmax(logits, axis=-1))
        if self.selection_bias:
            bias = ctx.buffers[self.key("selection_bias")]
            ctx.report(self.BIAS_ABSMAX, jnp.max(jnp.abs(bias)))
            _, top_idx = jax.lax.top_k(
                jax.lax.stop_gradient(probs + bias.astype(jnp.float32)),
                self.top_k)
            # the chosen experts' scores by a compare and a sum, forward
            # and backward (:func:`_top_k`'s reason: no scatter)
            hit = top_idx[..., None] == jnp.arange(self.num_experts,
                                                   dtype=top_idx.dtype)
            top_vals = jnp.sum(jnp.where(hit, probs[..., None, :], 0.0),
                               axis=-1)
        else:
            top_vals, top_idx = _top_k(probs, self.top_k)
        if self.norm_topk:
            top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
        if ctx.training:
            one_hot = jax.nn.one_hot(top_idx, self.num_experts,
                                     dtype=jnp.float32)  # (B, T, k, E)
            # f_e: fraction of routing slots assigned to expert e;
            # P_e: mean router probability.  Switch aux = E · Σ f_e P_e is
            # minimized (=1) by uniform routing.
            fractions = jnp.mean(jnp.sum(one_hot, axis=2), axis=(0, 1))
            mean_probs = jnp.mean(probs, axis=(0, 1))
            ctx.buffer_updates[self.key("router_fraction")] = \
                fractions / self.top_k
            if self.selection_bias:
                load = self.key("selection_load")
                ctx.buffer_updates[load] = ctx.buffers[load] + \
                    fractions * (top_idx.shape[0] * top_idx.shape[1])
            if self.aux_loss_coef > 0.0:
                aux = self.num_experts * jnp.sum(
                    (fractions / self.top_k) * mean_probs)
                ctx.aux_losses.append(self.aux_loss_coef * aux)
        if self.routed_scale != 1.0:
            top_vals = top_vals * self.routed_scale
        return top_vals, top_idx

    def apply(self, x, ctx):
        if not self.latent:
            return self._routed(x, x, ctx) + self._shared(x, ctx)
        inner = jnp.matmul(x, self._p(ctx, "latent_down.weight").T)
        routed = jnp.matmul(self._routed(x, inner, ctx),
                            self._p(ctx, "latent_up.weight").T)
        return routed + self._shared(x, ctx)

    def _routed(self, x, inner, ctx):
        """The weighted sum of the chosen held experts' outputs: routed by
        ``x``, computed on ``inner`` (``x`` itself, or its latent)."""
        gated = self.activation != UNGATED
        w_gate = self._p(ctx, "experts.gate_proj.weight") if gated else None
        w_up = self._p(ctx, "experts.up_proj.weight")
        w_down = self._p(ctx, "experts.down_proj.weight")
        if self.dispatch == "dropless":
            return self._apply_dropless(inner, *self.route(x, ctx), w_gate,
                                        w_up, w_down, ctx)
        weights = self.router_weights(x, ctx).astype(x.dtype)
        if self.dispatch == "capacity":
            from penroz_tpu.parallel.mesh import EXPERT_AXIS
            ep_mesh = getattr(ctx, "ep_mesh", None)
            routed = None
            if ep_mesh is not None:
                ep = ep_mesh.shape.get(EXPERT_AXIS, 1)
                if ep > 1 and self.num_experts % ep == 0:
                    routed = self._apply_capacity_ep(
                        inner, weights, w_gate, w_up, w_down, ep_mesh)
            if routed is None:
                routed = self._apply_capacity(inner, weights, w_gate, w_up,
                                              w_down)
        else:
            u = jnp.einsum("btd,ehd->bteh", inner, w_up)
            g = jnp.einsum("btd,ehd->bteh", inner, w_gate) if gated else None
            hidden = _expert_hidden(self.activation, u, g)
            y = jnp.einsum("bteh,edh->bted", hidden, w_down)
            routed = jnp.einsum("bted,bte->btd", y, weights)
        return routed

    def _shared(self, x, ctx):
        """The always-on shared expert (Qwen2-MoE): an ordinary gated MLP,
        scaled by a per-token sigmoid gate unless ``shared_expert_gate`` is
        off; summed with the routed output.  0 without one."""
        if not self.shared_expert_size:
            return jnp.zeros((), x.dtype)
        sg = (None if self.activation == UNGATED else jnp.einsum(
            "btd,hd->bth", x, self._p(ctx, "shared_expert.gate_proj.weight")))
        su = jnp.einsum("btd,hd->bth", x,
                        self._p(ctx, "shared_expert.up_proj.weight"))
        shared = jnp.einsum(
            "bth,dh->btd", _expert_hidden(self.activation, su, sg),
            self._p(ctx, "shared_expert.down_proj.weight"))
        if not self.shared_expert_gate:
            return shared
        gate = jax.nn.sigmoid(jnp.einsum(
            "btd,od->bto", x, self._p(ctx, "shared_expert_gate.weight")))
        return gate * shared

    # -- dropless: every routed pair computed, by grouped products ----------

    # Rows to a tile of the grouped products (``ops/pallas/moe_gmm.py``): a
    # tile belongs to one expert, so each expert's group is padded to it.
    ROW_TILE = 128

    def dropless_plan(self, tokens: int, on_tpu: bool = False) -> dict:
        """The static sizes of the dropless path for ``tokens`` tokens.

        The (token, choice) pairs whose expert is held are laid out sorted
        by expert, each expert's group padded to ``ROW_TILE`` rows.  The one
        length that can never overflow, ``rows_bound`` (every token choosing
        as many held experts as it can, every group a tile short of full),
        is the length of the tile table only.  The activations live in a
        buffer of ``rows`` rows, a row a token (in whole tiles, at most the
        bound): the layout is walked ``rows`` at a time for as many rounds
        as the rows really routed need, at most ``rounds_bound``; a layer
        whose tokens meet one held expert each on average takes one.

        ``places`` is the most rows one token can have, ``combine`` how the
        rows come back to their tokens: ``runs`` (the TPU's kernel, a token
        tile reading each group's run of its rows once, wherever its
        scalars and copies fit the core: ``moe_combine.fits``) or ``take``
        (a token reads a row a place)."""
        from penroz_tpu.ops.pallas import moe_combine
        tile = self.ROW_TILE
        places = min(self.top_k, self.experts_held)
        bound = tokens * places + self.experts_held * (tile - 1)
        bound = -(-bound // tile) * tile
        rows = min(-(-tokens // tile) * tile, bound)
        rounds = -(-bound // rows)
        runs = on_tpu and moe_combine.fits(
            rows=rows, tokens=tokens, width=self.latent or self.in_features,
            groups=self.experts_held, places=places)
        return {"experts": self.num_experts, "held": self.experts_held,
                "first": self.first_expert, "top_k": self.top_k,
                "rows": rows, "row_tile": tile, "dispatch": self.dispatch,
                "rows_bound": rounds * rows, "rounds_bound": rounds,
                "places": places, "combine": "runs" if runs else "take",
                "latent": self.latent, "activation": self.activation}

    def _apply_dropless(self, x, top_vals, top_idx, w_gate, w_up, w_down,
                        ctx):
        """Σ over a token's chosen experts *that are held* of weight ·
        expert(x), no pair lost whatever the imbalance, and no row moved by
        an indexed write.

        Dense compares reduce the ``(tokens, top_k)`` choices to the held
        experts (:func:`_held_choices`: who chose whom and with what weight,
        tokens on the lanes), and one cumulative sum along the tokens is the
        stable counting sort (:func:`_dropless_layout`): a token's row in an
        expert's group is the group's offset plus how many tokens chose the
        expert up to it, in the layout of :meth:`dropless_plan`.
        :func:`_dropless_rows` walks the rows really routed a round at a
        time: find each row's token (a compare against the running count),
        read the tokens' activations, three grouped products
        (``ops/pallas/moe_gmm.py``), and bring the rows back to their tokens
        by reads (``combine``).  Time and memory follow the rows routed; the
        bound costs the tile table.  ``moe_dropped`` is the pairs the router
        sent to held experts less the real rows the rounds handed to the
        products, counted as they ran."""
        B, T, d = x.shape
        tokens, held = B * T, self.experts_held
        on_tpu = attn_ops._tpu_platform(x, ctx.platform)
        plan = self.dropless_plan(tokens, on_tpu)
        _record_plan("moe", **plan)
        hit, chosen, weight = _held_choices(
            top_vals.reshape(tokens, self.top_k),
            top_idx.reshape(tokens, self.top_k), self.first_expert, held)
        layout = _dropless_layout(hit, chosen, plan["row_tile"],
                                  plan["rows_bound"])
        routed_rows = jnp.sum(layout.sizes)
        cfg = _DroplessConfig(
            row_tile=plan["row_tile"], rows=plan["rows"], held=held,
            activation=self.activation, on_tpu=on_tpu,
            combine=plan["combine"])
        y, placed = _dropless_rows(
            cfg, x.reshape(tokens, d), weight, w_gate, w_up, w_down, layout,
            -(-layout.padded // plan["rows"]))
        for stat, value in zip(self.ROUTED, (
                routed_rows, layout.padded, jnp.max(layout.sizes),
                routed_rows - placed)):
            ctx.report(stat, value)
        return y.reshape(B, T, d)

    # Tokens per dispatch group.  One-hot dispatch costs
    # O(group_size · E · C) with C ∝ group_size/E, i.e. quadratic in the
    # group size — fixed-size groups (Mesh-TF/Switch "G groups of S
    # tokens") keep dispatch linear in total tokens and a small fraction
    # of the expert-MLP FLOPs (ratio ≈ group/(3·intermediate)).
    DISPATCH_GROUP = 512

    def _apply_capacity(self, x, weights, w_gate, w_up, w_down):
        """Capacity-packed dispatch: one-hot buffer einsums, static shapes.

        ``weights``: (B, T, E) dense combine weights (zeros off the top-k).
        The flattened batch splits into fixed-size groups; within each
        group a selected token takes the next slot in its expert's queue
        (cumsum order) and tokens past the per-group capacity
        ``C = ceil(top_k · group / E · capacity_factor)`` get an all-zero
        dispatch row, silently losing that expert's contribution (Switch
        token dropping, applied per group).
        """
        B, T, d = x.shape
        E = self.num_experts
        tokens = B * T
        group = min(tokens, self.DISPATCH_GROUP)
        # Pad up to a group multiple with masked rows (weights 0 → never
        # selected, never dispatched) so group size stays fixed for any
        # B·T — a shrinking-divisor fallback would silently degrade to
        # dense-level dispatch FLOPs on awkward (e.g. prime) token counts.
        padded = -(-tokens // group) * group
        n_groups = padded // group
        cap = int(math.ceil(self.top_k * group / E * self.capacity_factor))
        cap = max(1, min(cap, group))
        flat_x = x.reshape(tokens, d)
        flat_w = weights.reshape(tokens, E)
        if padded != tokens:
            pad = padded - tokens
            flat_x = jnp.concatenate(
                [flat_x, jnp.zeros((pad, d), flat_x.dtype)])
            flat_w = jnp.concatenate(
                [flat_w, jnp.zeros((pad, E), flat_w.dtype)])
        gx = flat_x.reshape(n_groups, group, d)
        gw = flat_w.reshape(n_groups, group, E)
        disp, combine = self._dispatch_plan(gw, cap, x.dtype)
        expert_in = jnp.einsum("gsec,gsd->gecd", disp, gx)
        gate = jnp.einsum("gecd,ehd->gech", expert_in, w_gate)
        up = jnp.einsum("gecd,ehd->gech", expert_in, w_up)
        out_e = jnp.einsum("gech,edh->gecd", self._act(gate) * up, w_down)
        y = jnp.einsum("gsec,gecd->gsd", combine, out_e)
        return y.reshape(padded, d)[:tokens].reshape(B, T, d)

    @staticmethod
    def _dispatch_plan(gw, cap, dtype):
        """(dispatch, combine) one-hot tensors, both (G, S, E, C), for
        grouped capacity routing: a selected token takes the next slot in
        its expert's per-group queue (cumsum order); tokens past ``cap``
        one-hot an out-of-range class → all-zero row → dropped."""
        sel = gw > 0
        pos = jnp.cumsum(sel.astype(jnp.int32), axis=1) - 1  # slot in queue
        slot = jnp.where(sel & (pos < cap), pos, cap)
        disp = jax.nn.one_hot(slot, cap, dtype=dtype)
        return disp, disp * gw[..., None]

    def _apply_capacity_ep(self, x, weights, w_gate, w_up, w_down, mesh):
        """Expert-parallel capacity dispatch: ``lax.all_to_all`` token
        routing over the mesh ``expert`` axis (GShard-style).

        Dispatch groups shard over the expert axis; each device packs its
        local groups' tokens into per-expert buffers, one all_to_all sends
        each expert's (capacity-bounded) buffers to the device owning that
        expert shard, the expert MLP runs on the local expert slice for
        every group, and the reverse all_to_all returns outputs for a
        local combine.  Same routing math as :meth:`_apply_capacity`
        (shared ``_dispatch_plan``), but the cross-device traffic is two
        all_to_alls of the packed buffers instead of the full-activation
        psum the einsum formulation compiles to under GSPMD (r04 EP
        census: 34 all-reduces, zero all-to-all, 7x the DP step time).
        Only the expert axis goes manual — data/model/sequence stay
        GSPMD-automatic, so the path composes with DP/TP meshes.
        """
        from jax.sharding import PartitionSpec as P
        from penroz_tpu.parallel.mesh import EXPERT_AXIS
        ep = mesh.shape[EXPERT_AXIS]
        B, T, d = x.shape
        E = self.num_experts
        tokens = B * T
        group = min(tokens, self.DISPATCH_GROUP)
        n_groups = -(-tokens // group)
        # Round the group count up to an ep multiple with fully masked
        # groups (weights 0 → all-zero dispatch) so the group dim splits
        # evenly over the axis; the waste is < 1 group per device.
        n_groups += (-n_groups) % ep
        padded = n_groups * group
        cap = int(math.ceil(self.top_k * group / E * self.capacity_factor))
        cap = max(1, min(cap, group))
        flat_x = x.reshape(tokens, d)
        flat_w = weights.reshape(tokens, E)
        if padded != tokens:
            pad = padded - tokens
            flat_x = jnp.concatenate(
                [flat_x, jnp.zeros((pad, d), flat_x.dtype)])
            flat_w = jnp.concatenate(
                [flat_w, jnp.zeros((pad, E), flat_w.dtype)])
        # The expert-manual split gets its OWN leading dim (ep, G/ep, …):
        # Shardy rejects a dimension whose sharding mixes a free axis
        # before a manual one (e.g. the group dim co-sharded (data,
        # expert) inside the GPipe schedule), so no dim may carry both.
        gx = flat_x.reshape(ep, n_groups // ep, group, d)
        gw = flat_w.reshape(ep, n_groups // ep, group, E)

        def body(gx_l, gw_l, wg_l, wu_l, wd_l):
            # gx_l: (1, G/ep, S, d); gw_l: (1, G/ep, S, E) — local
            # groups, all experts.  wg_l/wu_l: (E/ep, h, d).
            disp, combine = self._dispatch_plan(gw_l[0], cap, gx_l.dtype)
            expert_in = jnp.einsum("gsec,gsd->gecd", disp, gx_l[0])
            # Send expert chunk p to device p; receive every device's
            # groups for the local experts: (G, E/ep, C, d).
            expert_in = jax.lax.all_to_all(expert_in, EXPERT_AXIS, 1, 0,
                                           tiled=True)
            gate = jnp.einsum("gecd,ehd->gech", expert_in, wg_l)
            up = jnp.einsum("gecd,ehd->gech", expert_in, wu_l)
            out_e = jnp.einsum("gech,edh->gecd", self._act(gate) * up, wd_l)
            # Return each group's outputs to its owner: (G/ep, E, C, d).
            out_e = jax.lax.all_to_all(out_e, EXPERT_AXIS, 0, 1, tiled=True)
            return jnp.einsum("gsec,gecd->gsd", combine, out_e)[None]

        spec4 = P(EXPERT_AXIS, None, None, None)
        spec3 = P(EXPERT_AXIS, None, None)
        y = jax.shard_map(body, mesh=mesh,
                          in_specs=(spec4, spec4, spec3, spec3, spec3),
                          out_specs=spec4,
                          axis_names=frozenset({EXPERT_AXIS}))(
            gx, gw, w_gate, w_up, w_down)
        return y.reshape(padded, d)[:tokens].reshape(B, T, d)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

class CausalSelfAttention(Module):
    """Causal self-attention over a fused QKV input with GQA + optional RoPE.

    Consumes a ``(B, T, q_dim + 2*kv_dim)`` projection (reference:
    neural_net_layers.py:59-95).  Head dim is derived from the input width.
    When a KV cache is present in the Ctx, new K/V are written at the current
    cache length (pre-GQA-expansion — unlike the reference, which expands KV
    heads before caching, we store only ``num_kv_heads`` heads in HBM).

    Layout.  Without a cache and without sequence parallelism the flash
    kernels run on the ``(B, T, ·)`` arrays as they are
    (:meth:`_apply_in_model_layout`): no slice-and-transpose to ``(B, H, T,
    D)`` and back.  Everything else — a KV cache, an SP axis or mesh, a
    logit softcap, head counts that leave a 128-lane block half full, GQA at
    head size 64, heads split over a mesh's ``model`` axis, no TPU, ``T``
    not a multiple of 128 — takes the ``(B, H, T, D)`` path below;
    ``ops/attention.py::stays_in_model_layout`` is the one place that
    decides, from what it observes.
    """

    def __init__(self, num_heads: int, dropout: float = 0.0,
                 num_kv_heads: Optional[int] = None,
                 rope_theta: Optional[float] = None,
                 head_dim: Optional[int] = None,
                 rope_scaling: Optional[dict] = None,
                 sliding_window: Optional[int] = None,
                 rope_pct: Optional[float] = None,
                 qk_norm: bool = False, qk_norm_eps: float = 1e-6,
                 qk_norm_scope: str = "head", rope_dim=None,
                 qk_norm_fp32_weight: bool = False, alibi: bool = False,
                 logit_softcap=None, attn_scale=None,
                 gate: Optional[str] = None):
        # ``gate="per_head"`` (headwise gating of the attention output,
        # arXiv:2505.06708): the fused projection carries ``num_heads``
        # more columns after [q | k | v], one gate logit a head, from the
        # same normed input (the last ``num_heads`` rows of that linear's
        # weight are W_g); the attention output of head h is multiplied by
        # sigmoid(logit_h) over its head_dim lanes, before the output
        # projection.
        if gate not in (None, "per_head"):
            raise ValueError(f"gate must be 'per_head' or absent, "
                             f"got {gate!r}")
        self.gate = gate
        if sliding_window is not None and int(sliding_window) < 1:
            raise ValueError(f"sliding_window must be >= 1, "
                             f"got {sliding_window}")
        # RMS normalization of q and k before RoPE.  scope="head" (Qwen3:
        # RMSNorm(head_dim) applied per head after the reshape, learned
        # (head_dim,) weights); scope="flat" (OLMo-2: RMSNorm over the
        # WHOLE projection before the head split, learned (H*hd,) /
        # (KV*hd,) weights).  Either way the module needs head_dim at
        # build time to size the weights.
        if qk_norm_scope not in ("head", "flat"):
            raise ValueError(f"qk_norm_scope must be 'head' or 'flat', "
                             f"got {qk_norm_scope!r}")
        self.qk_norm = bool(qk_norm)
        self.qk_norm_eps = float(qk_norm_eps)
        self.qk_norm_scope = qk_norm_scope
        # Weight-multiply precision order differs BY FAMILY in HF:
        # Qwen3RMSNorm (a LlamaRMSNorm copy) downcasts the normalized
        # activations to input dtype BEFORE multiplying the weight;
        # Olmo2RMSNorm multiplies the fp32 weight in fp32 and downcasts
        # once at the end.  A global choice skews bf16 imports of the
        # other family by one rounding step per element.
        self.qk_norm_fp32_weight = bool(qk_norm_fp32_weight)
        if self.qk_norm and head_dim is None:
            raise ValueError("qk_norm=True requires an explicit head_dim")
        self.sliding_window = (int(sliding_window)
                               if sliding_window is not None else None)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads) if num_kv_heads is not None else int(num_heads)
        self.dropout = float(dropout)
        # ALiBi (Press et al. 2022, BLOOM/MPT): per-head linear position
        # bias on the attention logits instead of rotary/learned
        # positions; slopes are a pure function of the head count.
        self.alibi = bool(alibi)
        if self.alibi and rope_theta is not None:
            raise ValueError("alibi and rope_theta are mutually exclusive "
                             "position encodings")
        # Gemma-2: score soft-capping c·tanh(s/c) and the
        # query_pre_attn_scalar^-0.5 scale override.
        if logit_softcap is not None and float(logit_softcap) <= 0.0:
            raise ValueError(f"logit_softcap must be > 0, "
                             f"got {logit_softcap}")
        self.logit_softcap = (float(logit_softcap)
                              if logit_softcap is not None else None)
        self.attn_scale = (float(attn_scale)
                           if attn_scale is not None else None)
        self.rope_theta = float(rope_theta) if rope_theta is not None else None
        self.head_dim = int(head_dim) if head_dim is not None else None
        # Partial rotary (GPT-NeoX rotary_pct): rotate only the first
        # int(head_dim * rope_pct) feature dims (rounded to even).
        if rope_pct is not None and not 0.0 < float(rope_pct) <= 1.0:
            raise ValueError(f"rope_pct must be in (0, 1], got {rope_pct}")
        self.rope_pct = float(rope_pct) if rope_pct is not None else None
        # Exact integer rotary width (GPT-J rotary_dim): overrides the
        # pct-derived value, whose float round-trip can drop 2 dims for
        # awkward (head_dim, rotary_dim) pairs.
        if rope_dim is not None and (int(rope_dim) < 2 or int(rope_dim) % 2):
            raise ValueError(f"rope_dim must be even and >= 2, "
                             f"got {rope_dim}")
        self.rope_dim = int(rope_dim) if rope_dim is not None else None
        # llama3-type inverse-frequency rescaling (ops/attention.rope_cos_sin).
        # Validated HERE, at model build time (→ HTTP 400 on POST /model/):
        # the DSL reaches this module directly, so the HF importer's guard
        # alone would let a yarn dict silently run the llama3 formula or a
        # missing key crash opaquely at first jit trace.
        rope_type = rope_scaling and (rope_scaling.get("rope_type")
                                      or rope_scaling.get("type")
                                      or "default")
        if rope_type == "linear":
            # HF linear scaling: positions divide by the factor (Gemma-3
            # global layers); no band parameters to validate.
            if float(rope_scaling.get("factor", 0.0)) < 1.0:
                raise ValueError("linear rope_scaling needs factor >= 1")
            self.rope_scaling = {"rope_type": "linear",
                                 "factor": float(rope_scaling["factor"])}
        elif rope_type == "yarn":
            self.rope_scaling = attn_ops.yarn_scaling(rope_scaling)
        elif rope_scaling:
            if rope_type != "llama3":
                raise ValueError(f"rope_scaling type {rope_type!r} is not "
                                 "supported (only 'llama3', 'linear' and "
                                 "'yarn')")
            missing = [k for k in ("factor",
                                   "original_max_position_embeddings")
                       if k not in rope_scaling]
            if missing:
                raise ValueError(f"rope_scaling missing keys: {missing}")
            low = float(rope_scaling.get("low_freq_factor", 1.0))
            high = float(rope_scaling.get("high_freq_factor", 4.0))
            if high <= low:
                # the band-smoothing divides by (high - low): equal factors
                # would NaN every logit at first forward (HF's
                # rope_config_validation rejects this too)
                raise ValueError(f"rope_scaling needs high_freq_factor > "
                                 f"low_freq_factor, got {low} >= {high}")
            if float(rope_scaling["factor"]) < 1.0:
                raise ValueError("rope_scaling factor must be >= 1")
            self.rope_scaling = {
                "rope_type": "llama3",
                "factor": float(rope_scaling["factor"]),
                "low_freq_factor":
                    float(rope_scaling.get("low_freq_factor", 1.0)),
                "high_freq_factor":
                    float(rope_scaling.get("high_freq_factor", 4.0)),
                "original_max_position_embeddings":
                    float(rope_scaling["original_max_position_embeddings"]),
            }
        else:
            self.rope_scaling = None
        self.layer_idx = 0  # assigned by the model builder

    def param_shapes(self):
        if not self.qk_norm:
            return {}
        if self.qk_norm_scope == "flat":
            return {"q_norm.weight": (self.num_heads * self.head_dim,),
                    "k_norm.weight": (self.num_kv_heads * self.head_dim,)}
        return {"q_norm.weight": (self.head_dim,),
                "k_norm.weight": (self.head_dim,)}

    def init(self, rng):
        return {self.key(name): jnp.ones(shape, jnp.float32)
                for name, shape in self.param_shapes().items()}

    def _head_rmsnorm(self, x, w):
        """fp32 RMS over the head dim, learned multiplicative weight."""
        xf = x.astype(jnp.float32)
        norm = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                             + self.qk_norm_eps)
        if self.qk_norm_fp32_weight:
            # Olmo2RMSNorm order: (weight * fp32_normed).to(input_dtype).
            return ((xf * norm) * w.astype(jnp.float32)).astype(x.dtype)
        # Qwen3/LlamaRMSNorm order: weight * normed.to(input_dtype).
        return ((xf * norm).astype(x.dtype) * w).astype(x.dtype)

    def _rotary_dim(self, head_dim: int):
        """Feature dims RoPE rotates, or None for all of them."""
        if self.rope_dim is not None:
            return None if self.rope_dim >= head_dim else self.rope_dim
        if self.rope_pct is not None and self.rope_pct < 1.0:
            return int(head_dim * self.rope_pct) // 2 * 2
        return None

    def _flat_norm(self, q_flat, k_flat, ctx):
        """OLMo-2: normalize the whole projection BEFORE the head split."""
        if self.qk_norm and self.qk_norm_scope == "flat":
            q_flat = self._head_rmsnorm(q_flat, self._p(ctx, "q_norm.weight"))
            k_flat = self._head_rmsnorm(k_flat, self._p(ctx, "k_norm.weight"))
        return q_flat, k_flat

    def rope_plan(self, batch: int, seq: int, head_dim: int, in_place: bool,
                  itemsize: int) -> dict:
        """How a call's rotation runs: ``path`` ``kernel``
        (``ops/pallas/rope.py``: q and k turned where they lie in one pass;
        ``in_place``: attention stays in ``(B, T, H·D)``
        (:meth:`_apply_in_model_layout`) on one TPU (:func:`_one_tpu`);
        and the kernel's tiles admit the shapes: whole heads of a multiple
        of 128 dims, all of them rotated) or ``xla``
        (``ops/attention.py::apply_rope`` on head-split views), and the
        ``bytes`` a rotation has to move: q and k read once and written
        once."""
        from penroz_tpu.ops.pallas import rope
        rotary = self._rotary_dim(head_dim)
        kernel = in_place and rope.fits(seq, head_dim, rotary)
        return {"path": "kernel" if kernel else "xla",
                "heads": self.num_heads, "kv_heads": self.num_kv_heads,
                "D": head_dim, "T": seq, "rotary_dim": rotary or head_dim,
                "bytes": rope.moved_bytes(batch, seq, self.num_heads,
                                          self.num_kv_heads, head_dim,
                                          itemsize)}

    def _head_norm_and_rope(self, q, k, ctx, offset, seq_axis: int,
                            rope: bool = True):
        """Per-head qk-norm, then (``rope``) RoPE from ``offset``, on
        head-split q, k whose sequence is axis ``seq_axis``: both are
        elementwise per head, so ``(B, H, T, D)`` and ``(B, T, H, D)`` serve
        alike."""
        if self.qk_norm and self.qk_norm_scope == "head":
            q = self._head_rmsnorm(q, self._p(ctx, "q_norm.weight"))
            k = self._head_rmsnorm(k, self._p(ctx, "k_norm.weight"))
        if rope and self.rope_theta is not None:
            q, k = attn_ops.apply_rope(
                q, k, self.rope_theta, offset, scaling=self.rope_scaling,
                rotary_dim=self._rotary_dim(q.shape[-1]), seq_axis=seq_axis)
        return q, k

    def _record_rope_plan(self, qkv, ctx, head_dim: int,
                          in_model_layout: bool) -> bool:
        """The rotation's plan of a traced layer on the record
        (:func:`_record_plan`); whether the kernel turns q and k."""
        B, T, _ = qkv.shape
        plan = self.rope_plan(
            B, T, head_dim, in_model_layout and _one_tpu(qkv, ctx.platform),
            qkv.dtype.itemsize)
        _record_plan("rope", **plan)
        return plan["path"] == "kernel"

    def _apply_in_model_layout(self, qkv, ctx, head_dim: int):
        """No cache, no sequence parallelism, shapes the ``btd`` flash entry
        takes: q, k, v never leave ``(B, T, ·)``.  With nothing between the
        projection and the kernels (no qk-norm, no RoPE) they read the fused
        array in place, and so does the rotation's kernel where
        :meth:`rope_plan` says ``kernel``; the norms, and the rotation where
        it says ``xla``, are elementwise per head and run on ``(B, T, H,
        D)`` views, no transpose."""
        B, T, _ = qkv.shape
        heads, kv_heads = self.num_heads, self.num_kv_heads
        q_dim, kv_dim = heads * head_dim, kv_heads * head_dim
        turned_here = (self.rope_theta is not None
                       and self._record_rope_plan(qkv, ctx, head_dim, True))
        q = k = None        # the fused array's lanes serve
        if self.qk_norm or (self.rope_theta is not None and not turned_here):
            q, k = self._flat_norm(qkv[..., :q_dim],
                                   qkv[..., q_dim:q_dim + kv_dim], ctx)
            q, k = self._head_norm_and_rope(
                q.reshape(B, T, heads, head_dim),
                k.reshape(B, T, kv_heads, head_dim), ctx, ctx.offset(),
                seq_axis=1, rope=not turned_here)
            q, k = q.reshape(B, T, q_dim), k.reshape(B, T, kv_dim)
        arrays = (qkv,) if q is None else (q, k, qkv[..., q_dim + kv_dim:])
        if turned_here:
            from penroz_tpu.ops.pallas import rope
            cos, sin = attn_ops.rope_cos_sin(
                head_dim, self.rope_theta, ctx.offset(), T, jnp.float32,
                scaling=self.rope_scaling)
            # the fused projection comes back as q, k (turned) and v; q and
            # k apart as the two of them, and v stays the slice it was
            arrays = rope.rotate(qkv if q is None else q, k, cos, sin,
                                 heads=heads, kv_heads=kv_heads) + arrays[2:]
        dropout_rate = self.dropout if ctx.training else 0.0
        return attn_ops.causal_attention_btd(
            *arrays, heads=heads, kv_heads=kv_heads,
            dropout_rate=dropout_rate,
            dropout_rng=ctx.next_rng() if dropout_rate > 0.0 else None,
            platform=ctx.platform, window=self.sliding_window,
            alibi=attn_ops.alibi_slopes(heads) if self.alibi else None,
            scale=self.attn_scale)

    def apply(self, x, ctx):
        if self.gate is None:
            return self._attend(x, ctx)
        B, T, _ = x.shape
        heads = self.num_heads
        out = self._attend(x[..., :-heads], ctx)
        gate = jax.nn.sigmoid(x[..., -heads:].astype(jnp.float32))
        out = out.reshape(B, T, heads, -1) * gate.astype(out.dtype)[..., None]
        return out.reshape(B, T, -1)

    def _attend(self, qkv, ctx):
        B, T, total_dim = qkv.shape
        head_dim = total_dim // (self.num_heads + 2 * self.num_kv_heads)
        q_dim = self.num_heads * head_dim
        kv_dim = self.num_kv_heads * head_dim

        if (ctx.kv is None and ctx.sp_manual_axis is None
                and ctx.sp_mesh is None
                and attn_ops.stays_in_model_layout(
                    qkv, T, head_dim, self.num_heads, self.num_kv_heads,
                    ctx.platform, self.logit_softcap)):
            return self._apply_in_model_layout(qkv, ctx, head_dim)
        if self.rope_theta is not None and ctx.kv is None:
            self._record_rope_plan(qkv, ctx, head_dim, False)

        q_flat, k_flat = self._flat_norm(qkv[..., :q_dim],
                                         qkv[..., q_dim:q_dim + kv_dim], ctx)
        q = q_flat.reshape(B, T, self.num_heads, head_dim)
        k = k_flat.reshape(B, T, self.num_kv_heads, head_dim)
        v = qkv[..., q_dim + kv_dim:].reshape(B, T, self.num_kv_heads, head_dim)
        # to (B, H, T, D)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))

        offset = ctx.offset()
        if self.rope_theta is not None and ctx.sp_manual_axis is not None:
            # Manual sequence sharding (GPipe×Ulysses): this shard
            # holds rows r·T_local..(r+1)·T_local-1 of the global
            # sequence — rotate with GLOBAL positions, not 0..T_local.
            offset = offset + jax.lax.axis_index(ctx.sp_manual_axis) * T
        q, k = self._head_norm_and_rope(q, k, ctx, offset, seq_axis=2)

        dropout_rate = self.dropout if ctx.training else 0.0
        dropout_rng = ctx.next_rng() if (dropout_rate > 0.0 and ctx.training) else None

        alibi = attn_ops.alibi_slopes(self.num_heads) if self.alibi else None

        if ctx.kv is not None:
            from penroz_tpu.ops import kv_cache as KV
            slot = self.layer_idx + ctx.layer_offset
            paged = isinstance(ctx.kv, KV.PagedKVState)
            ragged = paged and ctx.ragged_descs is not None
            if ragged:
                store_k, store_v = ctx.kv.append_packed(
                    slot, k, v, ctx.ragged_rows)
                length = None
            elif paged:
                store_k, store_v, length = ctx.kv.append_rows(slot, k, v)
            elif ctx.kv.quantized:
                # int8 cache: store + attend on the raw buffers — the
                # kernel dequantizes per VMEM tile, never materializing a
                # full-precision cache.
                store_k, store_v, length = ctx.kv.append_raw(slot, k, v)
            else:
                store_k, store_v, length = ctx.kv.append(slot, k, v)
            # int8 caches (paged pools and contiguous) carry per-token
            # scales; read AFTER the append so the new tokens' scales are in.
            scales = ({"k_scale": ctx.kv.k_scale[slot],
                       "v_scale": ctx.kv.v_scale[slot]}
                      if ctx.kv.quantized else {})
            if ragged:
                out = attn_ops.ragged_paged_cached_attention(
                    q, store_k, store_v, ctx.kv.block_table,
                    ctx.kv.page_size, ctx.ragged_descs,
                    platform=ctx.platform, window=self.sliding_window,
                    alibi=alibi, scale=self.attn_scale,
                    softcap=self.logit_softcap, **scales)
            elif paged:
                out = attn_ops.paged_cached_attention(
                    q, store_k, store_v, ctx.kv.block_table, ctx.kv.page_size,
                    offset, length, dropout_rate=dropout_rate,
                    dropout_rng=dropout_rng, platform=ctx.platform,
                    window=self.sliding_window, alibi=alibi,
                    scale=self.attn_scale, softcap=self.logit_softcap,
                    **scales)
            else:
                out = attn_ops.cached_attention(q, store_k, store_v, offset,
                                                length,
                                                dropout_rate=dropout_rate,
                                                dropout_rng=dropout_rng,
                                                platform=ctx.platform,
                                                window=self.sliding_window,
                                                alibi=alibi,
                                                scale=self.attn_scale,
                                                softcap=self.logit_softcap,
                                                **scales)
        elif ctx.sp_manual_axis is not None and dropout_rate == 0.0:
            # Inside the GPipe schedule with the sequence axis manual: the
            # SP bodies run on the ambient axis (a nested shard_map is
            # impossible).  Same mode dispatch + divisibility fallback as
            # the sp_mesh path below.
            from penroz_tpu.parallel import alltoall_attention as a2a
            from penroz_tpu.parallel import ring_attention as ring
            n_seq = jax.lax.axis_size(ctx.sp_manual_axis)
            if (ctx.sp_mode == "alltoall" and alibi is None
                    and self.logit_softcap is None
                    and a2a.alltoall_supported(
                        q.shape[1], k.shape[1], n=n_seq)):
                out = a2a.alltoall_attention_manual(
                    q, k, v, axis_name=ctx.sp_manual_axis,
                    window=self.sliding_window,
                    platform=attn_ops.platform_of(ctx.platform),
                    scale=self.attn_scale)
            else:
                if ctx.sp_mode == "alltoall":
                    # Trace-time (shapes are static), so the operator gets
                    # a signal — mirrors the sp_mesh path's warning.
                    # (ALiBi also lands here: the Ulysses body re-shards
                    # HEADS, whose slopes would become device-dynamic.)
                    logging.getLogger(__name__).warning(
                        "alltoall SP unavailable (heads Hq=%d/Hkv=%d vs "
                        "axis %d, or alibi bias); falling back to ring "
                        "attention", q.shape[1], k.shape[1], n_seq)
                out = ring.ring_attention_manual(
                    q, k, v, axis_name=ctx.sp_manual_axis,
                    window=self.sliding_window, alibi=alibi,
                    scale=self.attn_scale, softcap=self.logit_softcap)
        elif ctx.sp_mesh is not None and dropout_rate == 0.0:
            # Sequence-parallel training over ICI (windowed when the model
            # slides — long-context SP is exactly where windows matter).
            # Two modes: 'ring' rotates K/V via ppermute; 'alltoall'
            # (Ulysses) re-partitions seq→head sharding so each device runs
            # the ordinary fused kernel on the full sequence for its heads
            # (falls back to ring when heads don't divide the axis).
            from penroz_tpu.parallel import alltoall_attention as a2a
            from penroz_tpu.parallel.ring_attention import ring_attention
            if (ctx.sp_mode == "alltoall" and alibi is None
                    and self.logit_softcap is None
                    and a2a.alltoall_supported(q.shape[1], k.shape[1],
                                               ctx.sp_mesh)):
                # its body runs the kernel per shard, inside its own map
                out = a2a.alltoall_attention(
                    q, k, v, ctx.sp_mesh, causal=True,
                    window=self.sliding_window,
                    platform=attn_ops.platform_of(ctx.platform),
                    scale=self.attn_scale)
            else:
                if ctx.sp_mode == "alltoall":
                    # every fallback cause gets a trace-time signal, like
                    # the manual-axis branch
                    logging.getLogger(__name__).warning(
                        "alltoall SP unavailable (indivisible heads, "
                        "alibi, or logit softcap); falling back to ring "
                        "attention")
                out = ring_attention(q, k, v, ctx.sp_mesh, causal=True,
                                     window=self.sliding_window,
                                     alibi=alibi, scale=self.attn_scale,
                                     softcap=self.logit_softcap)
        else:
            out = attn_ops.causal_attention(q, k, v, dropout_rate=dropout_rate,
                                            dropout_rng=dropout_rng,
                                            platform=ctx.platform,
                                            window=self.sliding_window,
                                            alibi=alibi,
                                            scale=self.attn_scale,
                                            softcap=self.logit_softcap)

        return out.transpose(0, 2, 1, 3).reshape(B, T, q_dim)


class LatentAttention(Module):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1) on a
    normed ``(B, T, in_features)`` input, with its own projections::

        c_q = RMSNorm(x W_qa)  (q_rank);   [q_nope | q_rope] = c_q W_qb
        [c_kv | k_rope] = x W_kva  (kv_rank + d_rope);  c_kv = RMSNorm(c_kv)
        [k_nope | v] = c_kv W_kvb;   k_rope ONE vector a token, all heads'
        RoPE (rotate-half) on q_rope and k_rope over ``d_rope`` dims
        o = causal softmax([q_nope | q_rope] · [k_nope | k_rope] · scale) v
        out = concat(o) W_o

    a head ``d_nope + d_rope`` wide for the scores and ``d_v`` for the
    values; W_qb's rows a head [nope | rope], W_kvb's a head [nope | v],
    heads contiguous; no bias.  ``rope_scaling`` of type ``yarn`` blends the
    frequencies (``ops/attention.py::_yarn_inv_freq``) and carries its
    factor on the scores: ``scale = (d_nope + d_rope)^-½ ·
    m(mscale_all_dim)²`` with ``m(s) = 0.1 · s · ln(factor) + 1``, cos and
    sin times ``m(mscale) / m(mscale_all_dim)``.

    This is the *expanded* form, what training and an uncached forward
    take: every head's keys and values are made from the latent vector and
    go through ``ops/attention.py::causal_attention`` (the flash kernels at
    unlike score and value widths on a TPU).  The absorbed form, scores
    against ``c_kv`` itself, is the cache's and is not written: a KV cache
    refuses this module (``CompiledArch.refuse_latent``)."""

    def __init__(self, in_features: int, num_heads: int, q_rank: int,
                 kv_rank: int, d_nope: int, d_rope: int, d_v: int,
                 rope_theta: float = 10000.0,
                 rope_scaling: Optional[dict] = None, eps: float = 1e-6,
                 init_std: float = 0.02,
                 out_init_std: Optional[float] = None):
        self.in_features, self.num_heads = int(in_features), int(num_heads)
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.d_nope, self.d_rope, self.d_v = int(d_nope), int(d_rope), int(d_v)
        if self.d_rope % 2:
            raise ValueError(f"d_rope must be even, got {d_rope}")
        self.rope_theta = float(rope_theta)
        # the two norms of the bottlenecks: ``q_a_norm``, ``kv_a_norm``
        self.q_a_norm = RMSNorm(self.q_rank, eps)
        self.kv_a_norm = RMSNorm(self.kv_rank, eps)
        self.init_std = float(init_std)
        self.out_init_std = float(init_std if out_init_std is None
                                  else out_init_std)
        self.softmax_scale = (self.d_nope + self.d_rope) ** -0.5
        self.rope_scaling = None
        if rope_scaling:
            kind = rope_scaling.get("rope_type") or rope_scaling.get("type")
            if kind != "yarn":
                raise ValueError(f"latentattention takes rope_scaling of "
                                 f"type 'yarn' or none, got {kind!r}")
            factor = float(rope_scaling["factor"])

            def m(s):
                return 0.1 * float(s) * math.log(factor) + 1.0 \
                    if factor > 1 else 1.0

            all_dim = m(rope_scaling.get("mscale_all_dim", 0))
            self.softmax_scale *= all_dim * all_dim
            self.rope_scaling = attn_ops.yarn_scaling({
                **{k: rope_scaling[k] for k in (
                    "factor", "original_max_position_embeddings",
                    "beta_fast", "beta_slow") if k in rope_scaling},
                "attention_factor": m(rope_scaling.get("mscale", 1))
                / all_dim})

    def children(self):
        return [("q_a_norm", self.q_a_norm), ("kv_a_norm", self.kv_a_norm)]

    def param_shapes(self):
        d, H = self.in_features, self.num_heads
        return {
            "q_a_proj.weight": (self.q_rank, d),
            "q_b_proj.weight": (H * (self.d_nope + self.d_rope), self.q_rank),
            "kv_a_proj.weight": (self.kv_rank + self.d_rope, d),
            "kv_b_proj.weight": (H * (self.d_nope + self.d_v), self.kv_rank),
            "o_proj.weight": (d, H * self.d_v)}

    def init(self, rng):
        shapes = self.param_shapes()
        keys = jax.random.split(rng, len(shapes))
        out = {}
        for k, (name, shape) in zip(keys, shapes.items()):
            std = (self.out_init_std if name == "o_proj.weight"
                   else self.init_std)
            out[self.key(name)] = std * jax.random.normal(k, shape,
                                                          jnp.float32)
        return out

    def apply(self, x, ctx):
        if ctx.kv is not None:
            raise ValueError("latent attention has no KV-cache path (the "
                             "absorbed form is not written)")
        B, T, _ = x.shape
        H, dn, dr, dv = self.num_heads, self.d_nope, self.d_rope, self.d_v
        _record_plan("latent", heads=H, q_rank=self.q_rank,
                     kv_rank=self.kv_rank, d_nope=dn, d_rope=dr, d_v=dv,
                     softmax_scale=round(self.softmax_scale, 6), T=T,
                     path="expanded")
        c_q = self.q_a_norm.apply(
            jnp.matmul(x, self._p(ctx, "q_a_proj.weight").T), ctx)
        q = jnp.matmul(c_q, self._p(ctx, "q_b_proj.weight").T)
        latent = jnp.matmul(x, self._p(ctx, "kv_a_proj.weight").T)
        c_kv = self.kv_a_norm.apply(latent[..., :self.kv_rank], ctx)
        kv = jnp.matmul(c_kv, self._p(ctx, "kv_b_proj.weight").T)
        q = q.reshape(B, T, H, dn + dr)
        kv = kv.reshape(B, T, H, dn + dv)
        cos, sin = attn_ops.rope_cos_sin(dr, self.rope_theta, ctx.offset(),
                                         T, x.dtype,
                                         scaling=self.rope_scaling)
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
        turn = lambda t: t * cos + attn_ops._rotate_half(t) * sin
        q_rope = turn(q[..., dn:])
        k_rope = turn(latent[:, :, None, self.kv_rank:])    # (B, T, 1, dr)
        heads_first = lambda t: t.transpose(0, 2, 1, 3)
        q = heads_first(jnp.concatenate([q[..., :dn], q_rope], axis=-1))
        k = heads_first(jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (B, T, H, dr))],
            axis=-1))
        v = heads_first(kv[..., dn:])
        o = attn_ops.causal_attention(q, k, v, platform=ctx.platform,
                                      scale=self.softmax_scale)
        o = heads_first(o).reshape(B, T, H * dv)
        return jnp.matmul(o, self._p(ctx, "o_proj.weight").T)


class GatedSSM(Module):
    """Gated linear-attention / SSD token mixer with O(1) per-row state.

    Consumes a fused projection laid out ``[q (H·dk) | k (H·dk) | v (H·dv)
    | gate (H)]`` — the SSM analogue of attention's fused qkv Linear — and
    runs the recurrence ``S_t = σ(gate_t)·S_{t-1} + k_t ⊗ v_t,
    y_t = q_t·S_t`` (ops/ssm.py).  No positional encoding: the recurrence
    itself is the position signal, so the layer needs no RoPE/offset.

    Cached serving rides ``ctx.kv.ssm`` (the fixed-size
    :class:`~penroz_tpu.ops.ssm.SSMState` child of any KV variant) through
    the same dense / packed-ragged dispatch as attention; without a cache
    the full-sequence form runs: the chunked Pallas kernel on TPU
    inference, and wherever a gradient is taken (and off the TPU) still the
    sequential ``lax.scan`` over tokens (``ops/ssm.py::gla_full``): the
    mixer that trains in chunks is :class:`Mamba2Mixer`.  ``layer_idx``
    indexes the model's *ssm* layers, assigned by the model builder like
    attention's (models/model.py).
    """

    def __init__(self, num_heads: int, head_dim: int,
                 value_dim: int | None = None):
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.value_dim = int(value_dim) if value_dim is not None \
            else int(head_dim)
        self.layer_idx = 0  # assigned by the model builder

    @property
    def fused_dim(self) -> int:
        """Input width the preceding fused Linear must produce."""
        return self.num_heads * (2 * self.head_dim + self.value_dim + 1)

    def apply(self, x, ctx):
        from penroz_tpu.ops import ssm as ssm_ops
        B, T, total = x.shape
        H, dk, dv = self.num_heads, self.head_dim, self.value_dim
        if total != self.fused_dim:
            raise ValueError(f"ssm fused input width {total} != expected "
                             f"{self.fused_dim} (H={H}, dk={dk}, dv={dv})")
        q = x[..., :H * dk].reshape(B, T, H, dk) * (dk ** -0.5)
        k = x[..., H * dk:2 * H * dk].reshape(B, T, H, dk)
        v = x[..., 2 * H * dk:2 * H * dk + H * dv].reshape(B, T, H, dv)
        # fp32 gate: σ saturates in bf16 after ~8 tokens of decay product
        g = jax.nn.sigmoid(
            x[..., 2 * H * dk + H * dv:].astype(jnp.float32)).reshape(B, T, H)

        ssm = getattr(ctx.kv, "ssm", None) if ctx.kv is not None else None
        if ssm is not None:
            if ctx.ragged_descs is not None:
                # packed slots per block = Tp // NB (build_descriptors
                # emits NB equal blocks of block_q slots)
                nb = ctx.ragged_descs.shape[0]
                y = ssm.update_packed(self.layer_idx, q, k, v, g,
                                      ctx.ragged_descs, T // nb)
            else:
                y = ssm.update_dense(self.layer_idx, q, k, v, g,
                                     ctx.offset())
        else:
            y = ssm_ops.gla_full(q, k, v, g, platform=ctx.platform,
                                 training=ctx.training)
        return y.reshape(B, T, H * dv).astype(x.dtype)


class Mamba2Mixer(Module):
    """A Mamba-2 mixer (SSD, arXiv:2405.21060) on a normed ``(B, T,
    in_features)`` input, with its own projections, whole or as one rank's
    share of its heads::

        [z | xBC | dt] = u W_in        d_in | d_in + 2·G·N | H   (d_in = H·P)
        xBC = silu(conv(xBC))          depthwise, causal, ``conv_kernel`` taps
        [x | B | C] = xBC              d_in | G·N | G·N
        Δ = softplus(dt + dt_bias);  A = −exp(A_log);  a = exp(Δ·A)
        S_t = a_t S_{t−1} + Δ_t x_t ⊗ B_t;   y_t = S_t C_t + D x_t
        y = GroupRMSNorm(y · silu(z))  G groups of d_in / G, gain d_in wide
        out = y W_out

    ``num_heads`` heads of ``head_dim`` (P) with a scalar decay each, B and
    C (``state_size`` N wide) shared by the ``num_heads / n_groups`` heads
    of a group; no projection bias, a convolution bias.  The recurrence runs
    a chunk of ``chunk_size`` tokens at a time with its own backward
    (``ops/ssm.py::ssd_chunked``); Δ, the decays and the state are float32.

    The share: ``heads_held`` heads from ``first_head`` (default all), whole
    groups of them, so the gated norm, which is a group's, needs nothing
    from another rank; every parameter is cut to the heads and groups held.
    What the absent heads would add to ``out`` is left out.

    Trains and runs uncached; the convolution's and the recurrence's state
    in a KV cache is not written (``CompiledArch.refuse_mixer``)."""

    DT_MAX = Stat("ssd_dt_max", "max", "ssd")
    LOG_DECAY_ABSMAX = Stat("ssd_log_decay_absmax", "max", "ssd")

    def __init__(self, in_features: int, num_heads: int, head_dim: int,
                 state_size: int, n_groups: int = 1, conv_kernel: int = 4,
                 chunk_size: int = 128, heads_held: Optional[int] = None,
                 first_head: int = 0, eps: float = 1e-5,
                 init_std: float = 0.02,
                 out_init_std: Optional[float] = None,
                 dt_min: float = 0.001, dt_max: float = 0.1,
                 dt_floor: float = 1e-4):
        self.in_features, self.num_heads = int(in_features), int(num_heads)
        self.head_dim, self.state_size = int(head_dim), int(state_size)
        self.n_groups, self.conv_kernel = int(n_groups), int(conv_kernel)
        self.chunk_size, self.eps = int(chunk_size), float(eps)
        if self.num_heads % self.n_groups:
            raise ValueError(f"num_heads={num_heads} is no multiple of "
                             f"n_groups={n_groups}")
        per_group = self.num_heads // self.n_groups
        held = self.num_heads if heads_held is None else int(heads_held)
        first = int(first_head)
        if (held < 1 or held % per_group or first % per_group
                or not 0 <= first <= self.num_heads - held):
            raise ValueError(
                f"heads_held={heads_held} from first_head={first_head} is "
                f"not whole groups of {per_group} heads within "
                f"{self.num_heads}")
        self.heads_held, self.first_head = held, first
        self.groups_held = held // per_group
        self.d_inner = held * self.head_dim
        self.bc_dim = self.groups_held * self.state_size
        self.init_std = float(init_std)
        self.out_init_std = float(init_std if out_init_std is None
                                  else out_init_std)
        self.dt_range = (float(dt_min), float(dt_max), float(dt_floor))

    def stats(self):
        return (self.DT_MAX, self.LOG_DECAY_ABSMAX)

    def param_shapes(self):
        d, conv = self.in_features, self.d_inner + 2 * self.bc_dim
        return {"in_proj.weight": (self.d_inner + conv + self.heads_held, d),
                "conv1d.weight": (conv, self.conv_kernel),
                "conv1d.bias": (conv,),
                "dt_bias": (self.heads_held,),
                "A_log": (self.heads_held,),
                "D": (self.heads_held,),
                "norm.weight": (self.d_inner,),
                "out_proj.weight": (d, self.d_inner)}

    def init(self, rng):
        """Projections N(0, ``init_std``) (the output's ``out_init_std``),
        the convolution as a torch ``Conv1d`` of its fan-in, ``A_log = log
        U(1, 16)``, ``dt_bias`` the inverse softplus of a log-uniform draw
        in [``dt_min``, ``dt_max``] floored at ``dt_floor``, D and the gain
        1 (the published modelling code's)."""
        shapes = self.param_shapes()
        k = dict(zip(shapes, jax.random.split(rng, len(shapes))))
        lo, hi, floor = self.dt_range
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            k["dt_bias"], shapes["dt_bias"], jnp.float32,
            math.log(lo), math.log(hi))), floor)
        bound = self.conv_kernel ** -0.5
        own = {
            "in_proj.weight": self.init_std * jax.random.normal(
                k["in_proj.weight"], shapes["in_proj.weight"], jnp.float32),
            "out_proj.weight": self.out_init_std * jax.random.normal(
                k["out_proj.weight"], shapes["out_proj.weight"], jnp.float32),
            "conv1d.weight": _uniform(k["conv1d.weight"],
                                      shapes["conv1d.weight"], bound),
            "conv1d.bias": _uniform(k["conv1d.bias"], shapes["conv1d.bias"],
                                    bound),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(
                k["A_log"], shapes["A_log"], jnp.float32, 1.0, 16.0)),
            "D": jnp.ones(shapes["D"], jnp.float32),
            "norm.weight": jnp.ones(shapes["norm.weight"], jnp.float32)}
        return {self.key(name): value for name, value in own.items()}

    def plan(self, batch: int, seq: int) -> dict:
        """The mixer's counters for ``(batch, seq)`` tokens: its sizes, the
        share, and the scan's (``ops/ssm.py::ssd_plan``)."""
        from penroz_tpu.ops import ssm as ssm_ops
        scan = ssm_ops.ssd_plan(seq, self.heads_held, self.groups_held,
                                self.head_dim, self.state_size,
                                self.chunk_size, batch)
        return {"heads": self.num_heads, "held": self.heads_held,
                "groups": self.groups_held, "state": self.state_size,
                "head_dim": self.head_dim, "chunk": self.chunk_size,
                "conv_kernel": self.conv_kernel, "T": seq,
                "path": scan["path"],
                "boundary_bytes": scan["boundary_bytes"]}

    def _conv(self, x, ctx):
        """Depthwise causal convolution a channel and its bias, float32:
        ``y_t = b + Σ_k w_k x_{t − (K − 1) + k}``, zeros before the
        sequence."""
        w = ctx.params[self.key("conv1d.weight")].astype(jnp.float32)
        bias = ctx.params[self.key("conv1d.bias")].astype(jnp.float32)
        K, T = self.conv_kernel, x.shape[1]
        padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
        return bias + sum(padded[:, k:k + T] * w[:, k] for k in range(K))

    def apply(self, x, ctx):
        from penroz_tpu.ops import ssm as ssm_ops
        if ctx.kv is not None:
            raise ValueError("a Mamba-2 mixer has no KV-cache path (its "
                             "convolution and recurrent state are not "
                             "written into the cache)")
        B, T, _ = x.shape
        H, P, G, N = (self.heads_held, self.head_dim, self.groups_held,
                      self.state_size)
        _record_plan("ssd", **self.plan(B, T))
        f32 = lambda name: ctx.params[self.key(name)].astype(jnp.float32)
        proj = jnp.matmul(x, self._p(ctx, "in_proj.weight").T)
        z = proj[..., :self.d_inner]
        xbc = jax.nn.silu(self._conv(proj[..., self.d_inner:-H],
                                     ctx)).astype(x.dtype)
        dt = jax.nn.softplus(proj[..., -H:].astype(jnp.float32)
                             + f32("dt_bias"))
        A = -jnp.exp(f32("A_log"))
        xs = xbc[..., :self.d_inner].reshape(B, T, H, P)
        Bm = xbc[..., self.d_inner:self.d_inner + G * N].reshape(B, T, G, N)
        Cm = xbc[..., self.d_inner + G * N:].reshape(B, T, G, N)
        if ctx.training:
            # how near a chunk's whole decay exp(Σ Δ·A) comes to underflow
            pad = -T % self.chunk_size
            whole = jnp.pad(dt * A, ((0, 0), (0, pad), (0, 0))).reshape(
                B, -1, self.chunk_size, H)
            ctx.report(self.LOG_DECAY_ABSMAX,
                       jnp.max(jnp.abs(jnp.sum(whole, axis=2))))
            ctx.report(self.DT_MAX, jnp.max(dt))
        y = ssm_ops.ssd_chunked(xs, dt, A, Bm, Cm, self.chunk_size)
        y = y + f32("D")[:, None] * xs.astype(jnp.float32)
        y = y.reshape(B, T, self.d_inner) * jax.nn.silu(
            z.astype(jnp.float32))
        grouped = y.reshape(B, T, G, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + self.eps)
        y = (grouped.reshape(B, T, self.d_inner)
             * f32("norm.weight")).astype(x.dtype)
        return jnp.matmul(y, self._p(ctx, "out_proj.weight").T)


class MixerBlock(Module):
    """One mixer a layer: ``x + mixer(norm(x))``, the layer of a stack whose
    layers are each a state-space mixer, an expert layer or attention
    (``hybrid_override_pattern``) and not attention-then-MLP.  In training
    the layer runs under :func:`_recomputed`: the backward keeps its input
    (and what the kernels name) and runs its inside again.  That is a
    property of the container, as :class:`Looped`'s."""

    def __init__(self, norm: Module, mixer: Module):
        self.norm, self.mixer = norm, mixer

    def children(self):
        return [("norm", self.norm), ("mixer", self.mixer)]

    def _apply(self, ctx, x):
        return x + self.mixer.apply(self.norm.apply(x, ctx), ctx)

    def apply(self, x, ctx):
        return _recomputed(self._apply, ctx, [self], x)
