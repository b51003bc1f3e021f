"""Fused chunked softmax cross-entropy for large vocabularies.

The reference computes CE through torch's fused ``F.cross_entropy`` path
(reference: neural_net_model.py:264-268); the naive JAX equivalent
(``logits.astype(f32)`` + optax) materializes a full fp32 ``(B, T, V)`` copy
of the logits and saves fp32 residuals for the backward — ~1.6 GB at B=8,
T=1024, V=50304, almost all of it HBM traffic rather than MXU work.

``fused_cross_entropy_rows`` (one loss a token) is a ``custom_vjp`` whose
forward saves only the original (bf16) logits, the integer targets, and the
per-row fp32 ``lse``; its backward takes one cotangent a row.
``fused_cross_entropy_mean`` is the mean built on it, and
``expected_exit_loss`` the loss of a model with several exits (a looped
stack, ops/modules.py::Looped), which weighs each token's losses by that
token's exit distribution:

- On TPU it dispatches to streaming Pallas kernels
  (ops/pallas/cross_entropy.py) that read the logits exactly once per pass.
- Elsewhere it streams row-chunks through a ``lax.scan`` (fp32 math in
  chunk-sized pieces) — this path is also the kernels' correctness oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

# What ``_fce_fwd`` calls its per-row logsumexp: kept by a ``jax.checkpoint``
# whose policy saves the name (ops/modules.py::Looped), whose backward then
# recomputes the logits and not the cross-entropy forward over them.
LSE_NAME = "penroz_ce_lse"
# Rows per jnp scan step: big enough to keep the VPU busy, small enough that
# the fp32 temporaries stay cache-sized.
_CHUNK_ROWS = 512


def _use_pallas(x2d, platform) -> bool:
    from penroz_tpu.ops.attention import _tpu_platform
    return (x2d.shape[-1] >= 1024
            and jnp.issubdtype(x2d.dtype, jnp.floating)
            and _tpu_platform(x2d, platform))


def pad_rows(x2d, t1d, chunk: int):
    """Pad rows to a multiple of ``chunk``; padded targets get the -1
    sentinel that every consumer (jnp scan masks, Pallas backward kernel)
    treats as 'no loss / zero gradient'.  Shared with
    ops/pallas/cross_entropy.py — keep the sentinel in sync."""
    n = x2d.shape[0]
    num_chunks = max(1, -(-n // chunk))
    pad = num_chunks * chunk - n
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
        t1d = jnp.pad(t1d, (0, pad), constant_values=-1)
    return x2d, t1d, num_chunks


def _jnp_forward(x2d, t1d, chunk_rows: int):
    """Per-row (lse, label_logit) via a row-chunked scan; fp32 (N, 1) each."""
    xp, tp, num_chunks = pad_rows(x2d, t1d, chunk_rows)
    v = xp.shape[-1]
    xc = xp.reshape(num_chunks, chunk_rows, v)
    tc = tp.reshape(num_chunks, chunk_rows)

    def step(_, chunk):
        cx, ct = chunk
        x = cx.astype(jnp.float32)
        m = jnp.max(x, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(x - m[:, None]), axis=-1))
        safe_t = jnp.maximum(ct, 0)
        ll = jnp.take_along_axis(x, safe_t[:, None], axis=-1)[:, 0]
        return None, (lse, ll)

    _, (lse, ll) = jax.lax.scan(step, None, (xc, tc))
    n = x2d.shape[0]
    return (lse.reshape(-1, 1)[:n], ll.reshape(-1, 1)[:n])


def _jnp_backward(x2d, t1d, lse, scale, chunk_rows: int):
    """(softmax - onehot) · scale from saved lse, row-chunked; ``scale`` is
    fp32 ``(N, 1)``, one cotangent a row (a scalar serves every row)."""
    xp, tp, num_chunks = pad_rows(x2d, t1d, chunk_rows)
    v = xp.shape[-1]
    pad = xp.shape[0] - x2d.shape[0]
    scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32).reshape(-1, 1),
                             (x2d.shape[0], 1))
    lp, sp = ((jnp.pad(a, ((0, pad), (0, 0))) for a in (lse, scale))
              if pad else (lse, scale))
    xc = xp.reshape(num_chunks, chunk_rows, v)
    tc = tp.reshape(num_chunks, chunk_rows)
    lc = lp.reshape(num_chunks, chunk_rows, 1)
    sc = sp.reshape(num_chunks, chunk_rows, 1)

    def step(_, chunk):
        cx, ct, cl, cs = chunk
        x = cx.astype(jnp.float32)
        p = jnp.exp(x - cl)
        safe_t = jnp.maximum(ct, 0)
        onehot = (jnp.arange(v, dtype=jnp.int32)[None, :] == safe_t[:, None])
        valid = (ct >= 0)[:, None]
        return None, jnp.where(valid, (p - onehot) * cs, 0.0).astype(cx.dtype)

    _, grads = jax.lax.scan(step, None, (xc, tc, lc, sc))
    return grads.reshape(-1, v)[: x2d.shape[0]]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_cross_entropy_rows(logits, targets, chunk_rows: int = _CHUNK_ROWS,
                             platform=None):
    """Integer-label CE of every token, fp32, in the shape of ``targets``,
    without fp32 blowup: ``lse − label logit`` a row.

    ``logits``: ``(..., V)`` float (bf16 stays bf16 in HBM); ``targets``:
    ``(...,)`` int.  Numerically equivalent (fp32 accumulation) to
    ``optax.softmax_cross_entropy_with_integer_labels(f32(logits), t)``.
    The backward takes one cotangent a row.  ``platform`` is the
    execution-placement hint forwarded to the Pallas gate (see
    ops/attention.py:_tpu_platform).
    """
    rows, _ = _fce_fwd(logits, targets, chunk_rows, platform)
    return rows


def _fce_fwd(logits, targets, chunk_rows: int, platform):
    v = logits.shape[-1]
    x2d = logits.reshape(-1, v)
    t1d = targets.reshape(-1).astype(jnp.int32)
    if _use_pallas(x2d, platform):
        from penroz_tpu.ops.attention import _on_shards
        from penroz_tpu.ops.pallas import cross_entropy as ce
        # rows split over the data axis, the vocabulary never
        lse, ll = _on_shards(ce.ce_forward, platform, ("b.", "b"),
                             ("b.", "b."), x2d, t1d)
    else:
        lse, ll = _jnp_forward(x2d, t1d, chunk_rows)
    lse = checkpoint_name(lse, LSE_NAME)
    return (lse - ll).reshape(targets.shape), (logits, targets, lse)


def _fce_bwd(chunk_rows: int, platform, residuals, gbar):
    logits, targets, lse = residuals
    v = logits.shape[-1]
    x2d = logits.reshape(-1, v)
    t1d = targets.reshape(-1).astype(jnp.int32)
    scale = gbar.astype(jnp.float32).reshape(-1, 1)
    if _use_pallas(x2d, platform):
        from penroz_tpu.ops.attention import _on_shards
        from penroz_tpu.ops.pallas import cross_entropy as ce
        grad = _on_shards(ce.ce_backward, platform, ("b.", "b", "b.", "b."),
                          "b.", x2d, t1d, lse, scale)
    else:
        grad = _jnp_backward(x2d, t1d, lse, scale, chunk_rows)
    t_tangent = np.zeros(targets.shape, dtype=jax.dtypes.float0)
    return grad.reshape(logits.shape), t_tangent


fused_cross_entropy_rows.defvjp(_fce_fwd, _fce_bwd)


def fused_cross_entropy_mean(logits, targets, chunk_rows: int = _CHUNK_ROWS,
                             platform=None):
    """Mean of :func:`fused_cross_entropy_rows` over all leading dims (each
    row's cotangent is then the same ``ḡ / N``)."""
    n = int(np.prod(targets.shape)) if targets.shape else 1
    return jnp.sum(fused_cross_entropy_rows(logits, targets, chunk_rows,
                                            platform)) / n


def exit_distribution(gate_logits):
    """Per-token exit distribution of a stack with ``R`` exits from its
    ``(R, ...)`` gate logits: ``λt = σ(g^t)``, ``p1 = λ1``, ``pt = λt ·
    Π_{j<t}(1 − λj)``, the last exit taking what is left, ``pR = Π_{j<R}(1 −
    λj)`` (its own gate is not read).  fp32; sums to 1 over axis 0."""
    lam = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)      # Π_{j<=t}(1 − λj)
    reach = jnp.concatenate([jnp.ones_like(lam[:1]), stay], axis=0)
    return jnp.concatenate([lam[:-1] * reach[:-1], reach[-1:]], axis=0)


def expected_exit_loss(ce_rows, gate_logits, entropy_weight: float):
    """Mean over tokens of ``Σt pt · CE_t + β · Σt pt · log pt`` (the
    expected task loss less ``β`` × the exit distribution's entropy; Zhu et
    al. 2025, "Scaling Latent Reasoning via Looped Language Models", Stage
    I), with ``ce_rows`` and ``gate_logits`` both ``(R, ...)``.

    Returns ``(loss, {"pass_loss": (R,), "exit_mass": (R,)})``: the mean CE
    of each exit and the mean exit distribution, for the counters.
    """
    p = exit_distribution(gate_logits)
    tokens = tuple(range(1, p.ndim))
    ce_rows = ce_rows.astype(jnp.float32)
    neg_entropy = jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    loss = jnp.mean(jnp.sum(p * ce_rows, axis=0)
                    + entropy_weight * neg_entropy)
    stats = {"pass_loss": jax.lax.stop_gradient(jnp.mean(ce_rows, tokens)),
             "exit_mass": jax.lax.stop_gradient(jnp.mean(p, tokens))}
    return loss, stats
