"""Plain Laguna (a sparse-expert language model) in ``jax.numpy``, whole or as
one rank's share of an expert- and tensor-parallel layer: weights from a
seed, forward, loss, gradients.

The yardstick for the Laguna configurations of the benchmark (poolside,
``https://huggingface.co/poolside/Laguna-S-2.1`` ``config.json``).  No
kernels, no sort, no cache: the routed experts are a loop (``lax.scan``) over
the experts held, each computed for every token and weighted by what the router gave it
(zero for a token that did not choose it).  Config keys in brackets; what the
config does not say is listed in the configuration file under ``assumed``::

    h = E[x]                                   E: vocab x d, no scaling
    for l = 1..L:
        h = h + Attn_l(N1l(h))                 pre-norm
        h = h + MLP_l(N2l(h))                  [mlp_layer_types]: dense | sparse
    z = Nf(h) W_head                           logits, head untied
    Attn_l, on a = N1l(h), with H_l query heads [num_attention_heads_per_layer]
          on KV K/V heads [num_key_value_heads] of size D [head_dim]:
          [q | k | v | g] = a W_qkvg           no bias; g: one logit a head
          rotate-half RoPE on the first D x partial_rotary_factor dims of q, k
          [rope_parameters][layer_types[l]]: plain (rope_theta) or YaRN
          (inverse frequencies blended per dim between theta^(-2i/dim) and
          the same over factor, a linear ramp between the dims that make
          beta_fast and beta_slow turns in original_max_position_embeddings;
          cos and sin times attention_factor)
          o = causal softmax(q k^T / sqrt(D)) v, query head j on K/V head
          j // (H_l / KV); sliding_attention: keys (t - sliding_window, t] only
          o_j <- o_j * sigmoid(g_j)            per-head output gate
          out = o W_o
    dense MLP: (silu(a Wg) * a Wu) Wd, width intermediate_size
    sparse MLP, on a = N2l(h):
          p = softmax(a W_r) over all E experts [num_experts of the model],
          float32;  (w, e) = top-k(p) [num_experts_per_tok];
          w <- w / sum(w) [norm_topk_prob];  w <- s w [moe_routed_scaling_factor]
          routed = sum over the chosen e THAT ARE HELD of
                   w_e (silu(a Wg_e) * a Wu_e) Wd_e   width moe_intermediate_size
          out = routed + (silu(a Sg) * a Su) Sd       the shared expert, ungated
    RMSNorm: x / sqrt(mean(x^2) + rms_norm_eps) * gamma
    loss: mean cross-entropy of z against the next token

**The share.**  A configuration may hold part of every layer: experts
``first_expert .. first_expert + num_experts - 1`` of ``num_experts_routed``
(the router still scores all of them), ``num_attention_heads_per_layer[l]``
query heads on ``num_key_value_heads`` K/V heads, ``vocab_size`` ids.  What
the absent experts, heads and ids would have added is left out here exactly
as in the program: both compute the partial sum that one rank of the
deployment holds before its exchange, and pass it on to the next layer.

Nothing here reads anything the program made: weights come from
:func:`init_params` (the benchmark's seed), and :func:`as_gpt2_custom` is the
one place that knows the program's parameter names (those of
``presets.laguna_custom``; the function keeps the name ``kinds/train.py``
calls).

Two things are done for room and change no arithmetic: every layer and the
head run under ``jax.checkpoint``, and the attention scores are taken a block
of query rows at a time (under ``lax.map``).

``dtype`` selects the precision everything is computed in, as in
``reference/gpt2.py``; the router's scores are float32 in every precision
(its operands rounded like any matmul's): the configuration states them so.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import (PRECISIONS, _with_precision, seed_key,
                                      tree_rel_error)

__all__ = ["dims", "init_params", "init_program_weights", "as_gpt2_custom",
           "loss_and_grad", "mean_loss_and_grad", "forward", "yarn_inv_freq",
           "tree_rel_error", "PRESET", "preset_args"]

QUERY_BLOCK = 512


def dims(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys
    (Hugging Face Laguna names; ``num_experts`` is the experts *held*,
    ``num_experts_routed`` the router's width, default the same)."""
    depth = int(cfg["num_hidden_layers"])
    lists = {k: tuple(cfg[k]) for k in (
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer")}
    if any(len(v) != depth for v in lists.values()):
        raise ValueError(f"the per-layer lists do not name {depth} layers")
    rope = tuple(sorted(
        (kind, tuple(sorted(spec.items())))
        for kind, spec in cfg["rope_parameters"].items()
        if isinstance(spec, dict)))
    return {"d": int(cfg["hidden_size"]), "depth": depth,
            "vocab": int(cfg["vocab_size"]),
            "block": int(cfg["max_position_embeddings"]),
            "head_dim": int(cfg["head_dim"]),
            "heads": lists["num_attention_heads_per_layer"],
            "kv_heads": int(cfg["num_key_value_heads"]),
            "layer_types": lists["layer_types"],
            "mlp_types": lists["mlp_layer_types"],
            "intermediate": int(cfg["intermediate_size"]),
            "experts": int(cfg.get("num_experts_routed",
                                   cfg["num_experts"])),
            "held": int(cfg["num_experts"]),
            "first": int(cfg.get("first_expert", 0)),
            "top_k": int(cfg["num_experts_per_tok"]),
            "moe_intermediate": int(cfg["moe_intermediate_size"]),
            "shared": int(cfg["shared_expert_intermediate_size"]),
            "scale": float(cfg["moe_routed_scaling_factor"]),
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "window": int(cfg["sliding_window"]),
            "rope": rope, "eps": float(cfg["rms_norm_eps"]),
            "published_layers": int(cfg.get("published", {}).get(
                "num_hidden_layers", depth))}


@jax.tree_util.register_pytree_node_class
class Weights:
    """The parameters (``params``: what is differentiated) with what the
    equations need besides and no array's shape says (``hyper``: a sorted
    tuple of pairs, static under ``jit``).  A gradient comes back in the
    same form."""

    def __init__(self, params: dict, hyper: tuple):
        self.params, self.hyper = params, hyper

    def tree_flatten(self):
        return (self.params,), self.hyper

    @classmethod
    def tree_unflatten(cls, hyper, children):
        return cls(children[0], hyper)


_HYPER = ("head_dim", "kv_heads", "layer_types", "mlp_types", "first",
          "top_k", "scale", "norm_topk", "window", "rope", "eps")
_SIZES = ("d", "depth", "vocab", "head_dim", "heads", "kv_heads",
          "mlp_types", "intermediate", "experts", "held", "moe_intermediate",
          "shared", "published_layers")


def _hyper(sizes: dict) -> tuple:
    return tuple(sorted((k, sizes[k]) for k in _HYPER))


def _init_arrays(key, *, d, depth, vocab, head_dim, heads, kv_heads,
                 mlp_types, intermediate, experts, held, moe_intermediate,
                 shared, published_layers):
    std = 0.02
    proj_std = std / (2 * published_layers) ** 0.5
    keys = iter(jax.random.split(key, 2 + 9 * depth))

    def normal(shape, s=std):
        return s * jax.random.normal(next(keys), shape, jnp.float32)

    params = {"wte": normal((vocab, d)), "head": normal((d, vocab)),
              "nf": jnp.ones((d,))}
    for i in range(depth):
        h = heads[i]
        layer = {"n1": jnp.ones((d,)), "n2": jnp.ones((d,)),
                 "qkvg_w": normal((d, (h + 2 * kv_heads) * head_dim + h)),
                 "o_w": normal((h * head_dim, d), proj_std)}
        if mlp_types[i] == "dense":
            layer.update(gate_proj=normal((d, intermediate)),
                         up_proj=normal((d, intermediate)),
                         down_proj=normal((intermediate, d), proj_std))
        else:
            layer.update(
                router=normal((d, experts)),
                e_gate=normal((held, d, moe_intermediate)),
                e_up=normal((held, d, moe_intermediate)),
                e_down=normal((held, moe_intermediate, d), proj_std),
                s_gate=normal((d, shared)), s_up=normal((d, shared)),
                s_down=normal((shared, d), proj_std))
        params[f"h{i}"] = layer
    return params


@functools.partial(jax.jit, static_argnames=_SIZES)
def _init(key, **sizes):
    return _init_arrays(key, **sizes)


def init_params(cfg: dict, seed: int) -> Weights:
    """Every weight held, in float32, made on the default device in one
    jitted call: N(0, 0.02), the residual projections (attention output,
    MLP / expert / shared-expert down) scaled by 1/sqrt(2 · the published
    depth), norm gains 1."""
    sizes = dims(cfg)
    return Weights(_init(seed_key(seed), **{k: sizes[k] for k in _SIZES}),
                   _hyper(sizes))


def as_gpt2_custom(weights, depth: int) -> dict:
    """The same weights (or a gradient) under the names and layouts of the
    program's ``presets.laguna_custom`` DSL (linear weights stored ``(out,
    in)``; the fused projection is [q | k | v | gate], heads contiguous; the
    expert stacks ``(held, out, in)``)."""
    params = weights.params if isinstance(weights, Weights) else weights
    out = {"layers.0.weight": params["wte"]}
    for i in range(depth):
        h, p = params[f"h{i}"], f"layers.{1 + i}"
        out.update({
            f"{p}.attn_block.0.weight": h["n1"],
            f"{p}.attn_block.1.weight": h["qkvg_w"].T,
            f"{p}.attn_block.3.weight": h["o_w"].T,
            f"{p}.mlp_block.0.weight": h["n2"]})
        m = f"{p}.mlp_block.1"
        if "router" in h:
            swap = lambda a: jnp.swapaxes(a, 1, 2)
            out.update({
                f"{m}.router.weight": h["router"].T,
                f"{m}.experts.gate_proj.weight": swap(h["e_gate"]),
                f"{m}.experts.up_proj.weight": swap(h["e_up"]),
                f"{m}.experts.down_proj.weight": swap(h["e_down"]),
                f"{m}.shared_expert.gate_proj.weight": h["s_gate"].T,
                f"{m}.shared_expert.up_proj.weight": h["s_up"].T,
                f"{m}.shared_expert.down_proj.weight": h["s_down"].T})
        else:
            out.update({f"{m}.gate_proj.weight": h["gate_proj"].T,
                        f"{m}.up_proj.weight": h["up_proj"].T,
                        f"{m}.down_proj.weight": h["down_proj"].T})
    out.update({f"layers.{depth + 1}.weight": params["nf"],
                f"layers.{depth + 2}.weight": params["head"].T})
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


def yarn_inv_freq(dim: int, theta: float, factor: float, original: float,
                  beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies over ``dim`` rotated dims (Peng et al.
    2023, arXiv:2309.00071, as published implementations compute them): dim
    pair i keeps theta^(-2i/dim) where it makes more than ``beta_fast``
    turns within ``original`` positions, takes that over ``factor`` where
    it makes fewer than ``beta_slow``, and a linear blend between (the two
    bounds taken to whole pairs, floor and ceiling)."""
    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        plain = theta ** (-2.0 * i / dim)
        ramp = min(1.0, max(0.0, (i - low) / (high - low)))
        out.append(plain / factor * ramp + plain * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def _rope(x, spec: dict):
    """Rotate-half RoPE on the first ``D × partial_rotary_factor`` dims of
    the last axis of ``(B, H, T, D)``; the rest pass through."""
    T, D = x.shape[-2:]
    dim = int(D * float(spec.get("partial_rotary_factor", 1))) // 2 * 2
    theta = float(spec["rope_theta"])
    if spec.get("rope_type", "default") == "yarn":
        inv = yarn_inv_freq(dim, theta, float(spec["factor"]),
                            float(spec["original_max_position_embeddings"]),
                            float(spec["beta_fast"]),
                            float(spec["beta_slow"]))
        amplitude = float(spec["attention_factor"])
    else:
        inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
        amplitude = 1.0
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = amplitude * jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = amplitude * jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot, rest = x[..., :dim], x[..., dim:]
    x1, x2 = rot[..., :dim // 2], rot[..., dim // 2:]
    turned = jnp.concatenate([-x2, x1], -1)
    rot = (rot.astype(jnp.float32) * cos
           + turned.astype(jnp.float32) * sin).astype(x.dtype)
    return jnp.concatenate([rot, rest], -1)


def _attention(q, k, v, window, mm):
    """Causal softmax(q k^T / sqrt(D)) v over ``(B, H, T, D)`` (K/V already
    repeated to the query heads), a block of query rows at a time
    (``lax.map``: one block's graph, not T / QUERY_BLOCK copies of it, which
    took the compiler minutes); with a ``window``, keys ``(t - window, t]``
    only."""
    T, D = q.shape[-2:]
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    scale = jnp.sqrt(jnp.asarray(D, q.dtype))
    cols = jnp.arange(T)
    keys_t = k.transpose(0, 1, 3, 2)

    def rows_of(args):
        start, queries = args                       # queries (B, H, block, D)
        rows = start + jnp.arange(block)
        s = mm(queries, keys_t) / scale
        seen = rows[:, None] >= cols[None, :]
        if window is not None:
            seen &= cols[None, :] > rows[:, None] - window
        s = jnp.where(seen, s, jnp.asarray(-jnp.inf, s.dtype))
        return mm(jax.nn.softmax(s, axis=-1), v)

    blocks = jnp.moveaxis(q.reshape(q.shape[:2] + (T // block, block, D)),
                          2, 0)
    out = jax.lax.map(rows_of, (jnp.arange(0, T, block), blocks))
    return jnp.moveaxis(out, 0, 2).reshape(q.shape)


def _swiglu(a, gate, up, down, mm):
    return mm(jax.nn.silu(mm(a, gate)) * mm(a, up), down)


def _sparse(h, a, *, first, top_k, scale, norm_topk, mm):
    """routed (over the held experts) + shared; ``a`` ``(B, T, d)``."""
    f32 = lambda t: t.astype(jnp.float32)
    p = jax.nn.softmax(mm(f32(a), f32(h["router"])), axis=-1)
    w, e = jax.lax.top_k(p, top_k)
    if norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = scale * w
    def add(out, expert):                       # one of the experts held
        j, gate, up, down = expert
        share = jnp.sum(jnp.where(e == first + j, w, 0.0), -1)  # (B, T)
        y = _swiglu(a, gate, up, down, mm)
        return out + share[..., None].astype(y.dtype) * y, None

    out, _ = jax.lax.scan(
        add, _swiglu(a, h["s_gate"], h["s_up"], h["s_down"], mm),
        (jnp.arange(h["e_gate"].shape[0]), h["e_gate"], h["e_up"],
         h["e_down"]))
    return out


def _layer(h, u, *, heads, kind, hyper, mm):
    B, T, d = u.shape
    D, kv = hyper["head_dim"], hyper["kv_heads"]
    a = _rmsnorm(u, h["n1"], hyper["eps"])
    fused = mm(a, h["qkvg_w"])
    q, k, v, g = jnp.split(
        fused, [heads * D, (heads + kv) * D, (heads + 2 * kv) * D], axis=-1)
    split = lambda t, n: t.reshape(B, T, n, D).transpose(0, 2, 1, 3)
    q, k, v = split(q, heads), split(k, kv), split(v, kv)
    spec = dict(dict(hyper["rope"])[kind])
    q, k = _rope(q, spec), _rope(k, spec)
    k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
    window = hyper["window"] if kind == "sliding_attention" else None
    o = _attention(q, k, v, window, mm)                     # (B, H, T, D)
    gate = jax.nn.sigmoid(g.astype(jnp.float32)).astype(o.dtype)
    o = o * gate.transpose(0, 2, 1)[..., None]
    u = u + mm(o.transpose(0, 2, 1, 3).reshape(B, T, -1), h["o_w"])
    a = _rmsnorm(u, h["n2"], hyper["eps"])
    if "router" in h:
        return u + _sparse(h, a, first=hyper["first"], top_k=hyper["top_k"],
                           scale=hyper["scale"],
                           norm_topk=hyper["norm_topk"], mm=mm)
    return u + _swiglu(a, h["gate_proj"], h["up_proj"], h["down_proj"], mm)


def _hidden(weights: Weights, tokens, heads: tuple, precision: str):
    """The final-normed activations and the matmul in use."""
    hyper = dict(weights.hyper)
    dtype, rnd = PRECISIONS[precision]
    mm = (jnp.matmul if rnd is None
          else lambda a, b: jnp.matmul(rnd(a), rnd(b)))
    params = jax.tree.map(lambda a: a.astype(dtype), weights.params)
    u = params["wte"][tokens]
    for i, kind in enumerate(hyper["layer_types"]):
        layer = jax.checkpoint(functools.partial(
            _layer, heads=heads[i], kind=kind, hyper=hyper, mm=mm))
        u = layer(params[f"h{i}"], u)
    return params, _rmsnorm(u, params["nf"], hyper["eps"]), mm


def _head_loss(head, h, y, *, mm):
    logp = jax.nn.log_softmax(mm(h, head).astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


def _loss(weights: Weights, x, y, heads, precision):
    params, h, mm = _hidden(weights, x, heads, precision)
    return jax.checkpoint(functools.partial(_head_loss, mm=mm))(
        params["head"], h, y)


@_with_precision
@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def forward(weights, tokens, *, heads: tuple, precision: str = "float32"):
    """``(B, T, vocab)`` logits of the full causal forward, float32."""
    params, h, mm = _hidden(weights, tokens, heads, precision)
    return mm(h, params["head"]).astype(jnp.float32)


@_with_precision
@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def loss_and_grad(weights, x, y, *, heads: tuple, precision: str = "float32"):
    """(loss, gradient in float32 as :class:`Weights`) of the mean
    cross-entropy of ``x`` (B, T) against ``y`` (B, T)."""
    value, grads = jax.value_and_grad(_loss)(weights, x, y, heads, precision)
    return value, jax.tree.map(lambda g: g.astype(jnp.float32), grads)


_add_into = jax.jit(lambda acc, g: jax.tree.map(jnp.add, acc, g),
                    donate_argnums=(0,))


def mean_loss_and_grad(weights, xs, ys, *, heads: tuple, rows: int,
                       precision: str = "float32"):
    """Loss and gradient averaged over ``xs``/``ys`` (N, T), taken ``rows``
    sequences at a time (the sum kept in place).  ``N`` must be a multiple
    of ``rows``."""
    n = xs.shape[0]
    if n % rows:
        raise ValueError(f"{n} sequences do not split into groups of {rows}")
    total, acc = 0.0, None
    for i in range(0, n, rows):
        value, grads = loss_and_grad(weights, xs[i:i + rows], ys[i:i + rows],
                                     heads=heads, precision=precision)
        total += float(value)
        acc = grads if acc is None else _add_into(acc, grads)
        del grads
    k = n // rows
    return total / k, jax.tree.map(lambda g: g / k, acc)


# ---------------------------------------------------------------------------
# the program's side: which preset builds this architecture, and with what
# ---------------------------------------------------------------------------

PRESET = "laguna_custom"


def preset_args(cfg: dict) -> dict:
    """Arguments of ``penroz_tpu.models.presets.laguna_custom`` for ``cfg``."""
    s = dims(cfg)
    rope = {kind: dict(spec) for kind, spec in s["rope"]}
    return {"d": s["d"], "head_dim": s["head_dim"],
            "layer_types": list(s["layer_types"]),
            "heads_per_layer": list(s["heads"]), "kv_heads": s["kv_heads"],
            "mlp_layer_types": list(s["mlp_types"]),
            "intermediate": s["intermediate"], "num_experts": s["experts"],
            "experts_held": s["held"], "first_expert": s["first"],
            "top_k": s["top_k"], "moe_intermediate": s["moe_intermediate"],
            "shared_intermediate": s["shared"], "vocab": s["vocab"],
            "window": s["window"], "rope": rope, "routed_scale": s["scale"],
            "norm_topk": s["norm_topk"], "eps": s["eps"],
            "published_layers": s["published_layers"]}
