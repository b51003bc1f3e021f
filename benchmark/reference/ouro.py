"""Plain Ouro (a looped language model) in ``jax.numpy``: weights from a seed,
forward, the exit loss, gradients.

The yardstick for the Ouro configurations of the benchmark (ByteDance,
``https://huggingface.co/ByteDance/Ouro-2.6B`` ``config.json``; Zhu et al.
2025, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741).  No kernels, no cache, no batching tricks.  Config keys in
brackets; what the config does not say is listed in the configuration file
under ``assumed``::

    h0 = E[x]                                   E: vocab x d, no scaling
    for t = 1..R  (R = total_ut_steps):
        u = h(t-1)
        for l = 1..L:                           the SAME L layers every pass
            u = u + N2l(Attn_l(N1l(u)))         sandwich norm
            u = u + N4l(MLP_l(N3l(u)))
        h(t) = Nf(u)                            the final norm, inside the loop
        z(t) = h(t) W_head                      logits, head untied
        g(t) = h(t) w_gate + b_gate             one scalar a token
    Attn: q, k, v = x Wq, x Wk, x Wv (no bias), heads x head_dim, rotate-half
          RoPE on all head_dim dims (rope_theta), causal
          softmax(q k^T / sqrt(head_dim)) v, then Wo.
    MLP:  (silu(x Wg) * x Wu) Wd, width intermediate_size.
    RMSNorm: x / sqrt(mean(x^2) + rms_norm_eps) * gamma   (plain, not 1 + gamma)
    exit distribution, per token: lam_t = sigmoid(g(t)); p_1 = lam_1;
          p_t = lam_t prod_{j<t}(1 - lam_j) for 1 < t < R;
          p_R = prod_{j<R}(1 - lam_j)
    loss, per token: sum_t p_t CE(z(t), y) + beta sum_t p_t log p_t
          (Stage I: the expected loss less beta x the entropy); mean over tokens

Nothing here reads anything the program made: weights come from
:func:`init_params` (the benchmark's seed), and :func:`as_gpt2_custom` is the
one place that knows the program's parameter names (those of
``presets.ouro_custom``; the function keeps the name ``kinds/train.py`` calls).

Two things are done for room and change no arithmetic: every application of
a layer and every exit runs under ``jax.checkpoint`` (the backward recomputes
what the forward computed, the same operations on the same values), and the
attention scores are taken a block of query rows at a time (each row's
softmax is over its whole key range either way).  So one row of 4096 tokens
fits beside the weights, their gradient and what an ended training job still
holds on the chip.

``dtype`` selects the precision everything is computed in, as in
``reference/gpt2.py``: float32 runs under
``jax.default_matmul_precision("highest")``; bfloat16 and the scaled-fp8
emulation are the *controls* of the comparison that decides ``correct``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import (PRECISIONS, _with_precision, seed_key,
                                      tree_rel_error)

__all__ = ["dims", "init_params", "init_program_weights", "as_gpt2_custom",
           "loss_and_grad", "mean_loss_and_grad", "exit_distribution",
           "forward", "tree_rel_error", "PRESET", "preset_args"]

QUERY_BLOCK = 512


def dims(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys
    (Hugging Face Ouro names)."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"d": d, "heads": heads,
            "depth": int(cfg["num_hidden_layers"]),
            "vocab": int(cfg["vocab_size"]),
            "block": int(cfg["max_position_embeddings"]),
            "head_dim": int(cfg.get("head_dim") or d // heads),
            "intermediate": int(cfg["intermediate_size"]),
            "steps": int(cfg["total_ut_steps"]),
            "rope_theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "entropy_weight": float(cfg["entropy_weight"])}


@jax.tree_util.register_pytree_node_class
class Weights:
    """The parameters (``params``, a dict of arrays: what is differentiated)
    with what the equations need besides and no array's shape says
    (``hyper``: steps, rope_theta, eps, entropy_weight, as a sorted tuple of
    pairs, static under ``jit``).  A gradient comes back in the same form."""

    def __init__(self, params: dict, hyper: tuple):
        self.params, self.hyper = params, hyper

    def tree_flatten(self):
        return (self.params,), self.hyper

    @classmethod
    def tree_unflatten(cls, hyper, children):
        return cls(children[0], hyper)


def _hyper(sizes: dict) -> tuple:
    return tuple(sorted((k, sizes[k]) for k in
                        ("steps", "rope_theta", "eps", "entropy_weight")))


def _init_arrays(key, *, d, heads, depth, vocab, head_dim, intermediate,
                 steps, **_):
    std = 0.02
    proj_std = std / (2 * depth * steps) ** 0.5
    keys = iter(jax.random.split(key, 3 + 5 * depth))

    def normal(shape, s):
        return s * jax.random.normal(next(keys), shape, jnp.float32)

    params = {"wte": normal((vocab, d), std), "head": normal((d, vocab), std),
              "gate_w": normal((d,), std), "gate_b": jnp.zeros(()),
              "nf": jnp.ones((d,))}
    for i in range(depth):
        params[f"h{i}"] = {
            "n1": jnp.ones((d,)), "n2": jnp.ones((d,)),
            "n3": jnp.ones((d,)), "n4": jnp.ones((d,)),
            "qkv_w": normal((d, 3 * heads * head_dim), std),
            "o_w": normal((heads * head_dim, d), proj_std),
            "gate_proj": normal((d, intermediate), std),
            "up_proj": normal((d, intermediate), std),
            "down_proj": normal((intermediate, d), proj_std)}
    return params


_SIZES = ("d", "heads", "depth", "vocab", "head_dim", "intermediate", "steps")


@functools.partial(jax.jit, static_argnames=_SIZES)
def _init(key, **sizes):
    return _init_arrays(key, **sizes)


def init_params(cfg: dict, seed: int) -> Weights:
    """Every weight of the model in float32, made on the default device in
    one jitted call: N(0, 0.02), the residual projections (attention output,
    MLP down) scaled by 1/sqrt(2 · depth · steps) as each is applied
    ``steps`` times, norm gains 1, the gate's bias 0."""
    sizes = dims(cfg)
    return Weights(_init(seed_key(seed), **{k: sizes[k] for k in _SIZES}),
                   _hyper(sizes))


def as_gpt2_custom(weights, depth: int) -> dict:
    """The same weights (or a gradient) under the names and layouts of the
    program's ``presets.ouro_custom`` DSL (linear weights stored ``(out,
    in)``; the fused projection is [q | k | v], heads contiguous)."""
    params = weights.params if isinstance(weights, Weights) else weights
    out = {"layers.0.weight": params["wte"]}
    for i in range(depth):
        h, p = params[f"h{i}"], f"layers.1.body.{i}"
        out.update({
            f"{p}.attn_block.0.weight": h["n1"],
            f"{p}.attn_block.1.weight": h["qkv_w"].T,
            f"{p}.attn_block.3.weight": h["o_w"].T,
            f"{p}.post_attn_norm.weight": h["n2"],
            f"{p}.mlp_block.0.weight": h["n3"],
            f"{p}.mlp_block.1.gate_proj.weight": h["gate_proj"].T,
            f"{p}.mlp_block.1.up_proj.weight": h["up_proj"].T,
            f"{p}.mlp_block.1.down_proj.weight": h["down_proj"].T,
            f"{p}.post_mlp_norm.weight": h["n4"]})
    out.update({"layers.1.norm.weight": params["nf"],
                "layers.1.head.weight": params["head"].T,
                "layers.1.gate.weight": params["gate_w"][None, :],
                "layers.1.gate.bias": params["gate_b"][None]})
    return out


@functools.partial(jax.jit, static_argnames=_SIZES)
def _init_for_program(key, **sizes):
    return as_gpt2_custom(_init_arrays(key, **sizes), sizes["depth"])


def init_program_weights(cfg: dict, seed: int) -> dict:
    """:func:`init_params` under the program's names, made in the same one
    jitted call (the reference's own layout is never held beside it)."""
    sizes = dims(cfg)
    return _init_for_program(seed_key(seed), **{k: sizes[k] for k in _SIZES})


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _rmsnorm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return y.astype(x.dtype) * g


def _rope(x, theta):
    """Rotate-half RoPE on all of the last axis of ``(B, H, T, D)``."""
    T, D = x.shape[-2:]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    rotated = jnp.concatenate([-x2, x1], -1)
    xf = x.astype(jnp.float32)
    return (xf * cos + rotated.astype(jnp.float32) * sin).astype(x.dtype)


def _attention(q, k, v, mm):
    """Causal softmax(q k^T / sqrt(D)) v over ``(B, H, T, D)``, a block of
    query rows at a time."""
    T, D = q.shape[-2:]
    scale = jnp.sqrt(jnp.asarray(D, q.dtype))
    cols = jnp.arange(T)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        rows = cols[start:start + QUERY_BLOCK]
        s = mm(q[..., start:start + QUERY_BLOCK, :],
               k.transpose(0, 1, 3, 2)) / scale
        s = jnp.where(rows[:, None] >= cols[None, :], s,
                      jnp.asarray(-jnp.inf, s.dtype))
        out.append(mm(jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out, axis=-2)


def _layer(h, u, *, heads, theta, eps, mm):
    B, T, d = u.shape
    a = _rmsnorm(u, h["n1"], eps)
    q, k, v = (t.reshape(B, T, heads, -1).transpose(0, 2, 1, 3)
               for t in jnp.split(mm(a, h["qkv_w"]), 3, axis=-1))
    o = _attention(_rope(q, theta), _rope(k, theta), v, mm)
    o = mm(o.transpose(0, 2, 1, 3).reshape(B, T, -1), h["o_w"])
    u = u + _rmsnorm(o, h["n2"], eps)
    a = _rmsnorm(u, h["n3"], eps)
    m = mm(jax.nn.silu(mm(a, h["gate_proj"])) * mm(a, h["up_proj"]),
           h["down_proj"])
    return u + _rmsnorm(m, h["n4"], eps)


def _exit(head, gate_w, gate_b, h, y, *, mm):
    """Per-token cross-entropy (None without ``y``), gate logit and logits
    of one exit, float32."""
    z = mm(h, head).astype(jnp.float32)
    g = (mm(h, gate_w[:, None])[..., 0] + gate_b).astype(jnp.float32)
    if y is None:
        return None, g, z
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], -1)[..., 0], g, None


def _passes(weights: Weights, tokens, heads: int, precision: str):
    """The ``steps`` exit activations ``h(t)`` and the matmul in use."""
    hyper = dict(weights.hyper)
    dtype, rnd = PRECISIONS[precision]
    mm = (jnp.matmul if rnd is None
          else lambda a, b: jnp.matmul(rnd(a), rnd(b)))
    params = jax.tree.map(lambda a: a.astype(dtype), weights.params)
    depth = sum(k[0] == "h" and k[1:].isdigit() for k in params)
    layer = jax.checkpoint(functools.partial(
        _layer, heads=heads, theta=hyper["rope_theta"], eps=hyper["eps"],
        mm=mm))
    u, hs = params["wte"][tokens], []
    for _ in range(hyper["steps"]):
        for i in range(depth):
            u = layer(params[f"h{i}"], u)
        u = _rmsnorm(u, params["nf"], hyper["eps"])
        hs.append(u)
    return params, hs, mm


def exit_distribution(gates):
    """``(R, ...)`` gate logits → the exit distribution over axis 0."""
    lam = jax.nn.sigmoid(gates)
    p, reach = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * reach)
        reach = reach * (1.0 - lam[t])
    return jnp.stack(p + [reach])


def _loss(weights: Weights, x, y, heads, precision):
    """(loss, (pass losses (R,), exit masses (R,)))."""
    params, hs, mm = _passes(weights, x, heads, precision)
    one = jax.checkpoint(functools.partial(_exit, mm=mm))
    ce, gates = zip(*(one(params["head"], params["gate_w"],
                          params["gate_b"], h, y)[:2] for h in hs))
    ce, p = jnp.stack(ce), exit_distribution(jnp.stack(gates))
    beta = dict(weights.hyper)["entropy_weight"]
    per_token = (jnp.sum(p * ce, 0)
                 + beta * jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), 0))
    tokens = tuple(range(1, ce.ndim))
    return jnp.mean(per_token), (jnp.mean(ce, tokens), jnp.mean(p, tokens))


@_with_precision
@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def forward(weights, tokens, *, heads: int, precision: str = "float32"):
    """``(R, B, T, vocab)`` logits and ``(R, B, T)`` gate logits of the full
    causal forward, every pass, float32 (small sizes only)."""
    params, hs, mm = _passes(weights, tokens, heads, precision)
    outs = [_exit(params["head"], params["gate_w"], params["gate_b"], h,
                  None, mm=mm) for h in hs]
    return (jnp.stack([z for _, _, z in outs]),
            jnp.stack([g for _, g, _ in outs]))


@_with_precision
@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def loss_and_grad(weights, x, y, *, heads: int, precision: str = "float32"):
    """(loss, gradient in float32 as :class:`Weights`, (pass losses, exit
    masses)) of the exit loss of ``x`` (B, T) against ``y`` (B, T)."""
    (value, stats), grads = jax.value_and_grad(_loss, has_aux=True)(
        weights, x, y, heads, precision)
    return value, jax.tree.map(lambda g: g.astype(jnp.float32), grads), stats


_add_into = jax.jit(lambda acc, g: jax.tree.map(jnp.add, acc, g),
                    donate_argnums=(0,))


def mean_loss_and_grad(weights, xs, ys, *, heads: int, rows: int,
                       precision: str = "float32", with_stats: bool = False):
    """Loss and gradient averaged over ``xs``/``ys`` (N, T), taken ``rows``
    sequences at a time (the sum kept in place).  ``N`` must be a multiple
    of ``rows``.  ``with_stats``: also the mean pass losses and exit
    masses."""
    n = xs.shape[0]
    if n % rows:
        raise ValueError(f"{n} sequences do not split into groups of {rows}")
    total, acc, stats = 0.0, None, None
    for i in range(0, n, rows):
        value, grads, part = loss_and_grad(
            weights, xs[i:i + rows], ys[i:i + rows], heads=heads,
            precision=precision)
        total += float(value)
        acc = grads if acc is None else _add_into(acc, grads)
        stats = part if stats is None else jax.tree.map(jnp.add, stats, part)
        del grads
    k = n // rows
    out = (total / k, jax.tree.map(lambda g: g / k, acc))
    return (*out, jax.tree.map(lambda s: s / k, stats)) if with_stats else out


# ---------------------------------------------------------------------------
# the program's side: which preset builds this architecture, and with what
# ---------------------------------------------------------------------------

PRESET = "ouro_custom"


def preset_args(cfg: dict) -> dict:
    """Arguments of ``penroz_tpu.models.presets.ouro_custom`` for ``cfg``."""
    sizes = dims(cfg)
    return {**{k: sizes[k] for k in _SIZES}, "rope_theta": sizes["rope_theta"],
            "eps": sizes["eps"], "entropy_weight": sizes["entropy_weight"]}
