"""Attention math: RoPE, causal (training/prefill) and cached (decode) paths.

All functions are pure and jit-traceable.  GQA is computed by reshaping the
query heads into ``(kv_heads, group)`` and contracting against un-expanded K/V
— no materialized head expansion (the reference expands KV heads to full query
head count before attending: neural_net_layers.py:76-81).

On TPU the causal path dispatches to a Pallas flash-attention kernel
(ops/pallas/flash_attention.py) when shapes allow; the jnp fallback below is
also the correctness oracle for the kernel tests.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from penroz_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

log = logging.getLogger(__name__)

_NEG_INF = -1e30


class Placement(NamedTuple):
    """The ``platform`` hint of a program GSPMD partitions over ``mesh``
    (more than one device).  A plain platform string — or None — is the
    hint of a single-device program; both forms are hashable and ride the
    jit-cache keys, so a meshed and an unmeshed engine never share a
    traced program."""
    platform: Optional[str]
    mesh: Mesh


def platform_of(hint) -> Optional[str]:
    """The platform string of a ``platform`` hint (what callers already
    inside a manual region pass on: their kernels need no further map)."""
    return hint.platform if isinstance(hint, Placement) else hint


def _on_shards(kernel, platform, dims_in, dims_out, *args):
    """Call a Pallas kernel — directly, or mapped over the shards of the
    hint's mesh.

    Mosaic kernels cannot be partitioned automatically: under ``jax.jit``
    over more than one device their lowering raises ("Please wrap the call
    in a shard_map") — on the chip only; interpret mode and the CPU
    backend never show it.  ``dims_in``/``dims_out`` name each array dim:
    ``b`` (batch rows → the ``data`` axis) and ``h`` (heads → ``model``)
    stay split when every such dim divides its axis, anything else is
    gathered first; mesh axes a spec leaves out compute replicated.
    """
    mesh = platform.mesh if isinstance(platform, Placement) else None
    if mesh is None:
        return kernel(*args)
    axis = {"b": DATA_AXIS, "h": MODEL_AXIS}
    split = {c: all(a.shape[i] % mesh.shape[ax] == 0
                    for a, dims in zip(args, dims_in)
                    for i, d in enumerate(dims) if d == c)
             for c, ax in axis.items()}
    spec = lambda dims: P(*(axis[d] if split.get(d) else None for d in dims))
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=tuple(map(spec, dims_in)),
        out_specs=(spec(dims_out) if isinstance(dims_out, str)
                   else tuple(map(spec, dims_out))),
        check_vma=False)(*args)


def _head_dim_name(alibi) -> str:
    """Heads stay split only without ALiBi: the kernels take the slopes as
    a static per-head table indexed by the LOCAL head id."""
    return "h" if alibi is None else "."


def _scale_args(k_scale, v_scale) -> tuple:
    """The int8 scale planes as trailing array operands of a mapped kernel
    (none for a float cache) …"""
    return () if k_scale is None else (k_scale, v_scale)


def _scale_kwargs(scales: tuple) -> dict:
    """… and back to the kernels' keyword form inside it."""
    return dict(zip(("k_scale", "v_scale"), scales or (None, None)))

# One-shot trace-time fallback signals (the alltoall-SP fallbacks in
# modules.py warn per occurrence; these run on every decode trace, so they
# warn once per process).  Tests re-arm by clearing the set.
_WARNED_ONCE: set = set()


def _warn_once(key: str, msg: str, *args):
    if key in _WARNED_ONCE:
        return
    _WARNED_ONCE.add(key)
    log.warning(msg, *args)


def _llama3_scale_inv_freq(inv_freq, scaling: dict):
    """Llama-3.1 frequency rescaling (HF ``_compute_llama3_parameters``):
    long-wavelength components divide by ``factor``, short ones pass
    through, and a smooth ramp interpolates between the two bands."""
    factor = float(scaling["factor"])
    low = float(scaling.get("low_freq_factor", 1.0))
    high = float(scaling.get("high_freq_factor", 4.0))
    orig = float(scaling["original_max_position_embeddings"])
    wavelen = 2.0 * np.pi / inv_freq
    smooth = (orig / wavelen - low) / (high - low)
    smoothed = (1.0 - smooth) / factor * inv_freq + smooth * inv_freq
    scaled = jnp.where(wavelen > orig / low, inv_freq / factor, inv_freq)
    is_medium = (wavelen <= orig / low) & (wavelen >= orig / high)
    return jnp.where(is_medium, smoothed, scaled)


def yarn_scaling(scaling: dict) -> dict:
    """A ``rope_scaling`` dict of type ``yarn`` checked and completed: the
    published defaults for what it leaves out (``beta_fast`` 32,
    ``beta_slow`` 1, ``attention_factor`` 0.1·ln(factor) + 1)."""
    missing = [k for k in ("factor", "original_max_position_embeddings")
               if k not in scaling]
    if missing:
        raise ValueError(f"rope_scaling type 'yarn' is not supported "
                         f"without {missing} (missing keys)")
    factor = float(scaling["factor"])
    if factor < 1.0:
        raise ValueError("rope_scaling factor must be >= 1")
    attention_factor = scaling.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return {"rope_type": "yarn", "factor": factor,
            "original_max_position_embeddings":
                float(scaling["original_max_position_embeddings"]),
            "beta_fast": float(scaling.get("beta_fast") or 32.0),
            "beta_slow": float(scaling.get("beta_slow") or 1.0),
            "attention_factor": float(attention_factor)}


def _yarn_inv_freq(dim: int, theta: float, scaling: dict):
    """YaRN (Peng et al. 2023, arXiv:2309.00071) inverse frequencies over
    ``dim`` rotated dims: a dim whose wavelength makes more than
    ``beta_fast`` turns within the original context keeps its frequency
    (extrapolation), one that makes fewer than ``beta_slow`` has it divided
    by ``factor`` (interpolation), a linear ramp over the dims between (the
    two bounds taken to whole dims, floor and ceiling)."""
    def correction_dim(rotations):
        return (dim * math.log(scaling["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extrapolated = 1.0 / (theta ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (extrapolated / scaling["factor"]) * ramp \
        + extrapolated * (1.0 - ramp)


def rope_cos_sin(head_dim: int, theta: float, offset, length: int, dtype,
                 scaling: Optional[dict] = None):
    """cos/sin tables of shape (length, head_dim) starting at ``offset`` —
    or (B, length, head_dim) when ``offset`` is a (B,) vector (ragged
    batches: each sequence rotates from its own position).  A (B, length)
    ``offset`` gives every token its OWN absolute position (the ragged
    packed batch, where adjacent packed slots belong to different
    sequences at unrelated positions).

    ``scaling``: an HF ``rope_scaling`` dict with ``rope_type='llama3'``
    rescales the inverse frequencies (Llama 3.1+ long-context models);
    ``'yarn'`` (:func:`yarn_scaling`) blends them per dim and multiplies
    cos and sin by its ``attention_factor``."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    amplitude = 1.0
    if scaling:
        rope_type = (scaling.get("rope_type") or scaling.get("type")
                     or "default")
        if rope_type == "linear":
            # HF LinearScalingRotaryEmbedding: positions divide by the
            # factor, equivalently inv_freq /= factor (Gemma-3 global
            # layers ship {'rope_type': 'linear', 'factor': 8.0}).
            inv_freq = inv_freq / float(scaling["factor"])
        elif rope_type == "yarn":
            inv_freq = _yarn_inv_freq(head_dim, theta, scaling)
            amplitude = scaling["attention_factor"]
        else:
            inv_freq = _llama3_scale_inv_freq(inv_freq, scaling)
    steps = jnp.arange(length, dtype=jnp.float32)
    offset = jnp.asarray(offset)
    if offset.ndim == 2:
        if offset.shape[1] != length:
            raise ValueError(f"per-token offset length {offset.shape[1]} "
                             f"!= sequence length {length}")
        t = offset.astype(jnp.float32)  # (B, length): explicit positions
    elif offset.ndim >= 1:
        t = offset.astype(jnp.float32)[:, None] + steps  # (B, length)
    else:
        t = offset.astype(jnp.float32) + steps
    freqs = t[..., None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return ((amplitude * jnp.cos(emb)).astype(dtype),
            (amplitude * jnp.sin(emb)).astype(dtype))


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(q, k, theta: float, offset, scaling: Optional[dict] = None,
               rotary_dim: Optional[int] = None, seq_axis: int = 2):
    """Apply rotary embeddings to (B, H, T, D) query/key tensors — or to
    (B, T, H, D) ones with ``seq_axis=1`` (the rotation is elementwise, so
    the layout is the caller's).

    ``rotary_dim`` < D applies partial rotary (GPT-NeoX/Pythia
    ``rotary_pct``): only the first ``rotary_dim`` feature dims are
    rotated, the rest pass through unchanged."""
    head_dim = q.shape[-1]

    def expand(tbl):
        # (L, rd) → (1, 1, L, rd); (B, L, rd) ragged → (B, 1, L, rd) —
        # the heads' axis of size 1 after the sequence's when that is first
        if seq_axis == 1:
            return tbl[:, :, None] if tbl.ndim == 3 else tbl[None, :, None]
        return tbl[:, None] if tbl.ndim == 3 else tbl[None, None]

    if rotary_dim is None or rotary_dim >= head_dim:
        cos, sin = rope_cos_sin(head_dim, theta, offset, q.shape[seq_axis],
                                q.dtype, scaling=scaling)
        cos, sin = expand(cos), expand(sin)
        q = q * cos + _rotate_half(q) * sin
        k = k * cos + _rotate_half(k) * sin
        return q, k
    cos, sin = rope_cos_sin(rotary_dim, theta, offset, q.shape[seq_axis],
                            q.dtype, scaling=scaling)
    cos, sin = expand(cos), expand(sin)
    q_rot, q_pass = q[..., :rotary_dim], q[..., rotary_dim:]
    k_rot, k_pass = k[..., :rotary_dim], k[..., rotary_dim:]
    q_rot = q_rot * cos + _rotate_half(q_rot) * sin
    k_rot = k_rot * cos + _rotate_half(k_rot) * sin
    return (jnp.concatenate([q_rot, q_pass], axis=-1),
            jnp.concatenate([k_rot, k_pass], axis=-1))


def alibi_slopes(num_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes (Press et al. 2022, the HF ``build_alibi_
    tensor`` closed form): geometric sequence ``2^(-8/n)`` powers for
    power-of-two head counts, interleaved from the next power of two
    otherwise."""
    import math

    def pow2(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start ** (i + 1) for i in range(n)]

    if math.log2(num_heads).is_integer():
        return np.asarray(pow2(num_heads), np.float32)
    closest = 2 ** int(math.floor(math.log2(num_heads)))
    extra = pow2(2 * closest)[0::2][:num_heads - closest]
    return np.asarray(pow2(closest) + extra, np.float32)


def _alibi_bias(slopes, q_pos, k_pos, num_kv_heads: int):
    """(…, Hkv, G, T, S) additive logit bias ``slope_h · (k - q)``.

    Softmax rows are shift-invariant, so this equals HF Bloom's
    ``slope_h · k`` form while keeping the biases ≤ 0 in the causal
    region (no large positive logits before masking).  ``q_pos``/
    ``k_pos``: (T, S)-broadcastable int arrays, or (B, T, S) ragged."""
    rel = (k_pos - q_pos).astype(jnp.float32)
    s = jnp.asarray(slopes, jnp.float32).reshape(num_kv_heads, -1)
    if rel.ndim == 3:  # ragged: (B, T, S) → (B, Hkv, G, T, S)
        return s[None, :, :, None, None] * rel[:, None, None]
    return s[:, :, None, None] * rel  # (Hkv, G, T, S)


def _group_query_heads(q, num_kv_heads: int):
    """(B, Hq, T, D) -> (B, Hkv, G, T, D) where G = Hq // Hkv."""
    B, Hq, T, D = q.shape
    group = Hq // num_kv_heads
    return q.reshape(B, num_kv_heads, group, T, D)


def _attend(q, k, v, mask, dropout_rate=0.0, dropout_rng=None, bias=None,
            scale=None, softcap=None):
    """Masked softmax attention with grouped query heads.

    q: (B, Hkv, G, T, D); k, v: (B, Hkv, S, D); mask: broadcastable to
    (B, Hkv, G, T, S) with True = attend; ``bias`` (same broadcast):
    additive pre-softmax logits (ALiBi); ``scale`` overrides the
    1/sqrt(D) score scaling (Gemma-2/3 ``query_pre_attn_scalar``);
    ``softcap`` applies Gemma-2 logit soft-capping ``c·tanh(s/c)`` after
    scaling, before bias/mask.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    # HIGHEST pins true-f32 dot precision for f32 inputs: attention softmax
    # is precision-sensitive and some backends default f32 dots to bf16-
    # class multiplies.  bf16 inputs keep the MXU-native default.
    precision = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    logits = jnp.einsum("bhgtd,bhsd->bhgts", q, k,
                        preferred_element_type=jnp.float32,
                        precision=precision) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    if bias is not None:
        logits = logits + bias
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhgts,bhsd->bhgtd", probs, v, precision=precision)


def causal_attention_reference(q, k, v, dropout_rate=0.0, dropout_rng=None,
                               window: Optional[int] = None,
                               alibi: Optional[np.ndarray] = None,
                               scale: Optional[float] = None,
                               softcap: Optional[float] = None):
    """Pure-jnp causal attention. q: (B, Hq, T, D); k: (B, Hkv, T, D); v:
    (B, Hkv, T, Dv), ``Dv`` = ``D`` but under latent attention.

    ``window``: sliding-window width — query t attends keys in
    ``(t - window, t]`` (HF Mistral/Gemma-2 semantics: the window *includes*
    the query position and the ``window - 1`` keys before it).
    ``alibi``: per-query-head slopes — linear position bias added to the
    logits instead of any rotary/learned positions."""
    B, Hq, T, D = q.shape
    num_kv_heads = k.shape[1]
    qg = _group_query_heads(q, num_kv_heads)
    q_pos = jnp.arange(T)[:, None]
    k_pos = jnp.arange(T)[None, :]
    mask = k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - int(window)
    bias = (None if alibi is None
            else _alibi_bias(alibi, q_pos, k_pos, num_kv_heads))
    out = _attend(qg, k, v, mask, dropout_rate, dropout_rng, bias=bias,
                  scale=scale, softcap=softcap)
    return out.reshape(B, Hq, T, v.shape[-1])


def causal_attention(q, k, v, dropout_rate=0.0, dropout_rng=None,
                     platform=None, window: Optional[int] = None,
                     alibi: Optional[np.ndarray] = None,
                     scale: Optional[float] = None,
                     softcap: Optional[float] = None):
    """Causal self-attention; dispatches to the Pallas kernel on TPU.

    ``platform`` is the caller's execution-placement hint ('tpu'/'cpu'/...).
    Inside jit the arrays are tracers, so without the hint the gate can only
    guess from global config — and a model explicitly placed on CPU on a
    TPU-attached host would dispatch kernels that cannot lower for CPU.

    ``alibi``: per-query-head slopes — the kernels add the linear
    position bias in-tile (SMEM slopes, same pattern as the dropout
    seed), so BLOOM/MPT-class models keep the fused path.  ``softcap``
    (Gemma-2 logit capping) routes the TRAINING path to the jnp
    reference — the flash backward has no capped-gradient variant yet;
    the decode kernels apply the cap in-tile, so serving stays fused.
    """
    if softcap is not None:
        # Trace-time, one-shot (matching the SP fallback-signal
        # convention): Gemma-2-class training/prefill silently losing the
        # fused path is a perf cliff the operator should see.
        _warn_once("softcap_reference",
                   "logit softcap: flash kernel unavailable for the "
                   "training/prefill path (no capped-gradient backward); "
                   "using the O(T^2) jnp reference")
        return causal_attention_reference(q, k, v, dropout_rate,
                                          dropout_rng, window=window,
                                          alibi=alibi, scale=scale,
                                          softcap=softcap)
    if _use_flash(q, k, platform, v):
        from penroz_tpu.ops.pallas import flash_attention as fa
        rate, seed = _flash_dropout(dropout_rate, dropout_rng)

        def kernel(q, k, v, seed):
            return fa.flash_attention(
                q, k, v, causal=True, dropout_rate=rate,
                seed=_shard_seed(seed, rate, platform), window=window,
                alibi=alibi, scale=scale)

        bhtd = "b" + _head_dim_name(alibi) + ".."
        return _on_shards(kernel, platform, (bhtd, bhtd, bhtd, ""), bhtd,
                          q, k, v, seed)
    return causal_attention_reference(q, k, v, dropout_rate, dropout_rng,
                                      window=window, alibi=alibi,
                                      scale=scale)


def _flash_dropout(dropout_rate, dropout_rng):
    """``(rate, seed)`` for the flash kernels.  They stay fused under
    dropout (the reference keeps fused SDPA with dropout): the kernel
    derives its keep-mask from an int32 seed via an in-kernel position
    hash — distributional parity with the bernoulli fallback, zero HBM
    mask traffic."""
    if dropout_rate > 0.0 and dropout_rng is not None:
        return float(dropout_rate), jax.random.randint(
            dropout_rng, (), 0, jnp.iinfo(jnp.int32).max, dtype=jnp.int32)
    return 0.0, jnp.zeros((), jnp.int32)


def _shard_seed(seed, rate: float, platform):
    """Inside a mapped kernel call: the hash mixes LOCAL (batch, head) ids,
    so give each shard its own seed or shards would repeat one another's
    masks."""
    if rate > 0.0 and isinstance(platform, Placement):
        for ax in (DATA_AXIS, MODEL_AXIS):
            seed = seed * 31 + jax.lax.axis_index(ax)
    return seed


def stays_in_model_layout(x, T: int, head_dim: int, heads: int,
                          kv_heads: int, platform=None,
                          softcap: Optional[float] = None) -> bool:
    """Whether no-cache causal attention over a ``(B, T, ·)`` projection
    ``x`` can run without leaving that layout (:func:`causal_attention_btd`).

    Decided from what is observed, never from a knob: the flash kernels
    apply at all (a TPU, ``T`` a multiple of 128, head size 64/128/256;
    no logit softcap: :func:`causal_attention` sends that to the jnp
    reference and says so itself); whole 128-lane
    blocks of heads, and at head size 64 as many K/V heads as query heads
    (``flash_attention.btd_refusal``); heads not split over a mesh's
    ``model`` axis (the lane blocks of a fused projection are not a dim
    ``_on_shards`` can split; a ``data``-only mesh splits the batch and
    stays).  A module the kernels would serve that still falls back says
    why, once."""
    if (softcap is not None or _flash_disabled()
            or not _tpu_platform(x, platform)
            or not _flash_shapes(T, head_dim, heads, kv_heads)):
        return False
    from penroz_tpu.ops.pallas import flash_attention as fa
    if (isinstance(platform, Placement)
            and platform.mesh.shape.get(MODEL_AXIS, 1) > 1):
        why = "heads are split over the mesh's model axis"
    else:
        why = fa.btd_refusal(head_dim, heads, kv_heads)
    if why:
        _warn_once(f"leaves_model_layout: {why}",
                   "attention leaves the (B, T, H·D) layout for "
                   "(B, H, T, D) kernels and the transposes around them: "
                   "%s", why)
    return not why


def causal_attention_btd(q, k=None, v=None, *, heads: int, kv_heads: int,
                         dropout_rate=0.0, dropout_rng=None, platform=None,
                         window: Optional[int] = None,
                         alibi: Optional[np.ndarray] = None,
                         scale: Optional[float] = None):
    """:func:`causal_attention` in the model's own layout, for shapes
    :func:`stays_in_model_layout` admits: ``q`` alone is the fused
    ``(B, T, (heads + 2·kv_heads)·D)`` projection, read in place; with
    ``k`` and ``v``, three ``(B, T, H·D)`` arrays.  Returns
    ``(B, T, heads·D)``.  Under a mesh the batch splits over ``data``."""
    from penroz_tpu.ops.pallas import flash_attention as fa
    rate, seed = _flash_dropout(dropout_rate, dropout_rng)
    arrays = (q,) if k is None else (q, k, v)

    def kernel(*args):
        *arrays, seed = args
        return fa.flash_attention_btd(
            *arrays, heads=heads, kv_heads=kv_heads, causal=True,
            dropout_rate=rate, seed=_shard_seed(seed, rate, platform),
            window=window, alibi=alibi, scale=scale)

    return _on_shards(kernel, platform, ("b..",) * len(arrays) + ("",),
                      "b..", *arrays, seed)


def cached_attention(q, k_full, v_full, offset, length,
                     dropout_rate=0.0, dropout_rng=None, platform=None,
                     k_scale=None, v_scale=None,
                     window: Optional[int] = None,
                     alibi: Optional[np.ndarray] = None,
                     scale: Optional[float] = None,
                     softcap: Optional[float] = None):
    """Attention over a preallocated KV cache.

    q: (B, Hq, T, D) new queries at positions ``offset + [0, T)``.
    k_full/v_full: (B, Hkv, S_max, D) cache contents after the current append.
    ``length`` is the total valid length (offset + T).  Keys at index j are
    attended when ``j <= offset + t`` (combined causal + validity mask).
    With ``k_scale``/``v_scale`` (B, Hkv, S_max, 1) the cache is int8
    (TurboQuant): the kernel dequantizes per VMEM tile; this jnp fallback
    dequantizes the dense view (also the numerical oracle).

    Dispatches to the Pallas decode kernel on TPU (compute bounded by the
    valid length, not S_max); this jnp path is its correctness oracle.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together "
                         "(int8 caches carry scales for both streams)")
    use_kernel = dropout_rate == 0.0 and _use_flash_decode(q, k_full,
                                                           platform)
    if not use_kernel and dropout_rate == 0.0:
        _, Hq, T, D = q.shape
        Hkv, S = k_full.shape[1], k_full.shape[2]
        if (T > 1 and S >= 128 and S % 128 == 0 and D in (64, 128, 256)
                and Hq % Hkv == 0 and (Hq // Hkv) * T > 512
                and not _flash_disabled()
                and _tpu_platform(q, platform)):
            # A multi-token chunk (chunked prefill) whose ONLY disqualifier
            # is the decode kernel's (Hq/Hkv)·T ≤ 512 tile budget runs the
            # dense jnp path over S_max — correct but a perf cliff; static
            # shapes, so this is trace-time like the softcap signal above.
            _warn_once("chunk_off_kernel",
                       "cached attention chunk (T=%d, Hq=%d, Hkv=%d) "
                       "exceeds the decode kernel's tile budget; using "
                       "the jnp reference — a smaller PENROZ_PREFILL_CHUNK "
                       "keeps chunked prefill on the fused path", T, Hq,
                       Hkv)
    if use_kernel:
        from penroz_tpu.ops.pallas import decode_attention as da
        bhsd = "b" + _head_dim_name(alibi) + ".."
        scales = _scale_args(k_scale, v_scale)

        def kernel(q, k_full, v_full, lengths, *scales):
            return da.decode_attention(q, k_full, v_full, 0, lengths,
                                       window=window, alibi=alibi,
                                       scale=scale, softcap=softcap,
                                       **_scale_kwargs(scales))

        # both decode kernels derive positions from the per-row lengths
        # alone (their ``offset`` parameter is unused), so only those cross
        # into the mapped call
        return _on_shards(kernel, platform,
                          (bhsd, bhsd, bhsd, "b") + (bhsd,) * len(scales),
                          bhsd, q, k_full, v_full,
                          da.normalize_lengths(length, q.shape[0]), *scales)
    if k_scale is not None:
        k_full = (k_full.astype(jnp.float32) * k_scale).astype(q.dtype)
        v_full = (v_full.astype(jnp.float32) * v_scale).astype(q.dtype)
    B, Hq, T, D = q.shape
    S = k_full.shape[2]
    num_kv_heads = k_full.shape[1]
    qg = _group_query_heads(q, num_kv_heads)
    key_idx = jnp.arange(S, dtype=jnp.int32)
    lengths = jnp.asarray(length, jnp.int32)
    if lengths.ndim >= 1:
        # Ragged batch (same contract as the kernels — an ARRAY length of
        # any size opts in, so a (1,) length with B=1 behaves identically
        # on the kernel and oracle paths): per-sequence valid lengths,
        # each row's queries sit at positions length_b - T + t; ``offset``
        # is ignored, exactly as the kernels derive it from length.
        from penroz_tpu.ops.pallas.decode_attention import normalize_lengths
        lengths = normalize_lengths(lengths, B)
        q_pos = (lengths[:, None] - T) + jnp.arange(T, dtype=jnp.int32)
        mask = key_idx[None, None, :] <= q_pos[:, :, None]  # (B, T, S)
        if window is not None:
            mask &= key_idx[None, None, :] > q_pos[:, :, None] - int(window)
        bias = (None if alibi is None
                else _alibi_bias(alibi, q_pos[:, :, None],
                                 key_idx[None, None, :], num_kv_heads))
        mask = mask[:, None, None]  # (B, 1, 1, T, S)
    else:
        q_pos = offset + jnp.arange(T, dtype=jnp.int32)
        mask = key_idx[None, :] <= q_pos[:, None]  # (T, S)
        if window is not None:
            mask &= key_idx[None, :] > q_pos[:, None] - int(window)
        bias = (None if alibi is None
                else _alibi_bias(alibi, q_pos[:, None], key_idx[None, :],
                                 num_kv_heads))
    out = _attend(qg, k_full, v_full, mask, dropout_rate, dropout_rng,
                  bias=bias, scale=scale, softcap=softcap)
    return out.reshape(B, Hq, T, D)


def paged_cached_attention(q, flat_k, flat_v, block_table, page_size: int,
                           offset, length, dropout_rate=0.0,
                           dropout_rng=None, platform=None,
                           k_scale=None, v_scale=None,
                           window: Optional[int] = None,
                           alibi: Optional[np.ndarray] = None,
                           scale: Optional[float] = None,
                           softcap: Optional[float] = None):
    """Cached attention over a paged KV pool (block table indirection).

    On TPU dispatches to the paged Pallas kernel — one physical page of K/V
    resident in VMEM at a time, so context length is HBM-bounded.  With
    ``k_scale``/``v_scale`` the pools are int8 (TurboQuant + paged) and the
    kernel dequantizes per page in VMEM.  The fallback (also the correctness
    oracle) gathers the dense (dequantized) view and reuses
    :func:`cached_attention`'s jnp path.
    """
    if dropout_rate == 0.0 and _use_paged_kernel(q, flat_k, block_table,
                                                 page_size, platform):
        from penroz_tpu.ops.pallas import paged_attention as pa
        from penroz_tpu.ops.pallas.decode_attention import normalize_lengths
        h = _head_dim_name(alibi)
        pool = h + ".."  # head-major pool shared by every batch row
        scales = _scale_args(k_scale, v_scale)

        def kernel(q, flat_k, flat_v, table, lengths, *scales):
            return pa.paged_decode_attention(q, flat_k, flat_v, table,
                                             page_size, 0, lengths,
                                             window=window, alibi=alibi,
                                             scale=scale, softcap=softcap,
                                             **_scale_kwargs(scales))

        return _on_shards(kernel, platform,
                          ("b" + h + "..", pool, pool, "b.", "b")
                          + (pool,) * len(scales), "b" + h + "..",
                          q, flat_k, flat_v, block_table,
                          normalize_lengths(length, q.shape[0]), *scales)
    B = q.shape[0]
    pages_per_seq = block_table.shape[1]
    max_len = pages_per_seq * page_size
    all_pos = jnp.arange(max_len, dtype=jnp.int32)
    phys = jnp.maximum(block_table[:, all_pos // page_size], 0)
    rows = phys * page_size + all_pos % page_size  # (B, max_len)
    # flat pools are head-major (Hkv, pool_rows, D)
    gather = lambda flat: jnp.take(flat, rows, axis=1,
                                   mode="clip").transpose(1, 0, 2, 3)
    if k_scale is not None:
        k_full = (gather(flat_k).astype(jnp.float32)
                  * gather(k_scale)).astype(q.dtype)
        v_full = (gather(flat_v).astype(jnp.float32)
                  * gather(v_scale)).astype(q.dtype)
    else:
        k_full, v_full = gather(flat_k), gather(flat_v)
    # Dense-gather fallback; cached_attention may still use the contiguous
    # decode kernel on the gathered views when shapes allow.
    return cached_attention(q, k_full, v_full, offset,
                            length, dropout_rate, dropout_rng,
                            platform=platform, window=window, alibi=alibi,
                            scale=scale, softcap=softcap)


def ragged_paged_attention_reference(q, flat_k, flat_v, block_table,
                                     page_size: int, descs,
                                     k_scale=None, v_scale=None,
                                     window: Optional[int] = None,
                                     alibi: Optional[np.ndarray] = None,
                                     scale: Optional[float] = None,
                                     softcap: Optional[float] = None):
    """Sequential-oracle attention for a PACKED mixed batch.

    q: (1, Hq, Tp, D) packed queries in descriptor order (Tp = num_descs
    · block_q); descs: (num_descs, 4) int32 ``(row, q_pos0, q_valid,
    kv_len)`` — see ops/pallas/ragged_paged_attention.py.  Gathers each
    descriptor's dense KV view through the block table and reuses
    :func:`_attend` with the per-token causal mask, so the result equals
    running each row's phase (prefill chunk / decode step / verify span)
    through :func:`paged_cached_attention` one at a time.  Padding slots
    (row = -1 or t ≥ q_valid) come back zero, matching the kernel.
    """
    _, Hq, Tp, D = q.shape
    Hkv = flat_k.shape[0]
    group = Hq // Hkv
    NB = descs.shape[0]
    BQ = Tp // NB
    pages_per_seq = block_table.shape[1]
    max_len = pages_per_seq * page_size
    descs = jnp.asarray(descs, jnp.int32)
    row = jnp.maximum(descs[:, 0], 0)
    all_pos = jnp.arange(max_len, dtype=jnp.int32)
    phys = jnp.maximum(block_table[row][:, all_pos // page_size], 0)
    rows = phys * page_size + all_pos % page_size  # (NB, max_len)
    gather = lambda flat: jnp.take(flat, rows, axis=1,
                                   mode="clip").transpose(1, 0, 2, 3)
    if k_scale is not None:
        k_dense = (gather(flat_k).astype(jnp.float32)
                   * gather(k_scale)).astype(q.dtype)
        v_dense = (gather(flat_v).astype(jnp.float32)
                   * gather(v_scale)).astype(q.dtype)
    else:
        k_dense, v_dense = gather(flat_k), gather(flat_v)
    # (1, Hq, Tp, D) → (NB, Hkv, group, BQ, D): one "batch" entry per
    # descriptor block (head order is kv-major, pure reshape + transpose).
    qg = q[0].reshape(Hkv, group, NB, BQ, D).transpose(2, 0, 1, 3, 4)
    t = jnp.arange(BQ, dtype=jnp.int32)
    q_abs = descs[:, 1:2] + t[None, :]                    # (NB, BQ)
    valid_q = (t[None, :] < descs[:, 2:3]) & (descs[:, 0:1] >= 0)
    k_idx = jnp.arange(max_len, dtype=jnp.int32)
    mask = valid_q[:, :, None] & (k_idx[None, None, :] <= q_abs[:, :, None])
    if window is not None:
        mask &= k_idx[None, None, :] > q_abs[:, :, None] - int(window)
    bias = (None if alibi is None
            else _alibi_bias(alibi, q_abs[:, :, None],
                             k_idx[None, None, :], Hkv))
    out = _attend(qg, k_dense, v_dense, mask[:, None, None], bias=bias,
                  scale=scale, softcap=softcap)
    # Fully-masked padding slots softmax to uniform in _attend; zero them
    # like the kernel (l = 0 → output 0) so parity is exact slot-for-slot.
    out = out * valid_q[:, None, None, :, None].astype(out.dtype)
    return out.transpose(1, 2, 0, 3, 4).reshape(1, Hq, Tp, D)


def ragged_paged_cached_attention(q, flat_k, flat_v, block_table,
                                  page_size: int, descs, platform=None,
                                  k_scale=None, v_scale=None,
                                  window: Optional[int] = None,
                                  alibi: Optional[np.ndarray] = None,
                                  scale: Optional[float] = None,
                                  softcap: Optional[float] = None):
    """Unified mixed-batch attention over a paged pool (the ragged
    serving fast path).

    On TPU dispatches to the ragged Pallas kernel — one dispatch covers
    prefill chunks, decode steps and spec-verify spans side by side,
    reading KV through the block table (ops/pallas/
    ragged_paged_attention.py).  The fallback (also the correctness
    oracle) gathers per-descriptor dense views.
    """
    if _use_ragged_kernel(q, flat_k, block_table, page_size, descs,
                          platform):
        from penroz_tpu.ops.pallas import ragged_paged_attention as rpa
        h = _head_dim_name(alibi)
        pool = h + ".."
        scales = _scale_args(k_scale, v_scale)

        def kernel(q, flat_k, flat_v, table, descs, *scales):
            return rpa.ragged_paged_attention(q, flat_k, flat_v, table,
                                              page_size, descs,
                                              window=window, alibi=alibi,
                                              scale=scale, softcap=softcap,
                                              **_scale_kwargs(scales))

        # one packed batch: descriptors address block-table rows by their
        # global index, so only heads split
        return _on_shards(kernel, platform,
                          ("." + h + "..", pool, pool, "..", "..")
                          + (pool,) * len(scales), "." + h + "..",
                          q, flat_k, flat_v, block_table,
                          jnp.asarray(descs, jnp.int32), *scales)
    return ragged_paged_attention_reference(q, flat_k, flat_v, block_table,
                                            page_size, descs,
                                            k_scale=k_scale,
                                            v_scale=v_scale, window=window,
                                            alibi=alibi, scale=scale,
                                            softcap=softcap)


def _use_ragged_kernel(q, flat_k, block_table, page_size: int, descs,
                       platform=None) -> bool:
    if _flash_disabled() or not _tpu_platform(q, platform):
        return False
    _, Hq, Tp, D = q.shape
    Hkv = flat_k.shape[0]
    NB = descs.shape[0]
    if NB == 0 or Tp % NB != 0:
        return False
    block_q = Tp // NB
    return (D in (64, 128, 256) and page_size % 8 == 0 and page_size >= 8
            and Hq % Hkv == 0 and (Hq // Hkv) * block_q <= 512)


def _use_paged_kernel(q, flat_k, block_table, page_size: int,
                      platform=None) -> bool:
    if _flash_disabled() or not _tpu_platform(q, platform):
        return False
    B, Hq, T, D = q.shape
    Hkv = flat_k.shape[0]
    return (D in (64, 128, 256) and page_size % 8 == 0 and page_size >= 8
            and Hq % Hkv == 0 and (Hq // Hkv) * T <= 512)


def _flash_disabled() -> bool:
    """PENROZ_DISABLE_FLASH=1 disables the Pallas *attention* kernels only —
    other Pallas consumers (fused CE, embedding backward) gate on
    :func:`_tpu_platform` directly so an attention A/B stays isolated."""
    import os
    return os.environ.get("PENROZ_DISABLE_FLASH", "0") == "1"


def _tpu_platform(x, platform=None) -> bool:
    """Whether computation on ``x`` will run on TPU (pure platform check).

    ``platform`` — the caller's placement hint — wins when given.  Otherwise:
    a concrete array knows its device; a tracer doesn't, and
    ``jax.default_backend()`` reports the highest-priority backend even when
    ``jax_default_device`` pins computation elsewhere (e.g. CPU tests on a
    TPU-attached host), so the config is consulted before the backend.
    Nothing here is caught: a gate that cannot tell where it runs must
    raise, not quietly pick the jnp reference over the kernel.
    """
    platform = platform_of(platform)
    if platform is None:
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            platform = next(iter(x.devices())).platform
        else:
            dev = jax.config.jax_default_device
            if dev is None:
                platform = jax.default_backend()
            elif isinstance(dev, str):  # modern JAX accepts platform strings
                platform = dev
            else:
                platform = dev.platform
    return platform == "tpu"


def _use_flash(q, k, platform=None, v=None) -> bool:
    """Whether the Pallas flash kernel applies to these shapes/platform.  A
    shape the kernels would serve on this TPU but for its sizes says so once
    (a model that silently left the kernels is seen in the job's log)."""
    if _flash_disabled() or not _tpu_platform(q, platform):
        return False
    B, Hq, T, D = q.shape
    Dv = D if v is None else v.shape[-1]
    fits = _flash_shapes(T, D, Hq, k.shape[1], Dv)
    if not fits:
        _warn_once(f"off_flash: {T} {D} {Dv} {Hq} {k.shape[1]}",
                   "attention leaves the flash kernels for the O(T^2) jnp "
                   "path: T=%d (D, Dv)=(%d, %d) heads=%d on %d (they take T "
                   "a multiple of 128 and (D, Dv) of (64, 64), (128, 128), "
                   "(256, 256) or (192, 128))", T, D, Dv, Hq, k.shape[1])
    return fits


# (score width, value width) of a head the flash kernels take: one width, or
# latent attention's 192-wide scores over 128-wide values
_FLASH_WIDTHS = ((64, 64), (128, 128), (256, 256), (192, 128))


def _flash_shapes(T: int, D: int, Hq: int, Hkv: int,
                  Dv: Optional[int] = None) -> bool:
    # MXU-friendly: head dim multiple of 128 lane requirement handled by the
    # kernel via padding; sequence must be long enough to tile.
    return (T >= 128 and T % 128 == 0
            and (D, D if Dv is None else Dv) in _FLASH_WIDTHS
            and Hq % Hkv == 0)


def _use_flash_decode(q, k_full, platform=None) -> bool:
    """Whether the Pallas decode kernel applies (static shape checks only —
    offset/length are traced)."""
    if _flash_disabled() or not _tpu_platform(q, platform):
        return False
    B, Hq, T, D = q.shape
    Hkv, S = k_full.shape[1], k_full.shape[2]
    # K/V stream through the kernel grid one tile at a time, so S is
    # HBM-bounded (no VMEM gate) — bandwidth tracks the valid length via
    # the clamped index map, not S_max.
    return (S >= 128 and S % 128 == 0 and D in (64, 128, 256)
            and Hq % Hkv == 0 and (Hq // Hkv) * T <= 512)
