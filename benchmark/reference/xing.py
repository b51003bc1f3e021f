"""Plain Xing4.0 (a sparse-expert language model with latent attention and a
multi-stream residual) in ``jax.numpy``, whole or as one rank's share of an
expert-parallel layer: weights from a seed, forward, loss, gradients.

The yardstick for the Xing4.0 configurations of the benchmark (XingChen-AGI,
``https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B`` ``config.json``).  No
kernels, no sort, no cache: attention is the expanded form of latent
attention (DeepSeek-V2, arXiv:2405.04434 section 2.1) with the whole score
matrix of a block of query rows, the routed experts are a loop (``lax.scan``)
over the experts held, each computed for every token and weighted by what the
router gave it.  Config keys in brackets; what the config does not say is
listed in the configuration file under ``assumed``::

    X = [E[x]] * n                             n streams [hc_mult], copies
    for l = 1..L:
        X = HC_l,a(X, h -> Attn_l(N1l(h)))     one mixing a sub-block
        X = HC_l,m(X, h -> MLP_l(N2l(h)))      dense for l <= first_k_dense_replace
    z = Nf(sum_i X[i]) W_head                  logits, head untied

    HC(X, F), X (T, n, d)  (manifold-constrained hyper-connections,
    arXiv:2512.24880, on hyper-connections, arXiv:2409.19606):
        xt = vec(X) / rms(vec(X))              n*d wide, float32, [rms_norm_eps]
        u = xt Phi                             Phi (n*d, 2n + n^2)
        Hpre  = sigmoid(a_pre  u[0:n]  + b_pre)
        Hpost = 2 sigmoid(a_post u[n:2n] + b_post)
        Hres  = Sinkhorn(clip(a_res mat(u[2n:]) + b_res,
                              [mhc_h_res_clamp_min], [mhc_h_res_clamp_max]))
        Sinkhorn: M = exp(.), then [hc_sinkhorn_iters] times
                  M <- M / (column sums + [hc_eps]); M <- M / (row sums + [hc_eps])
        y = F(sum_i Hpre[i] X[i])
        X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y
    Attn, on a = N1(h), H heads [num_attention_heads]:
        cq = Nq(a W_qa)  [q_lora_rank];  [q_nope | q_rope] = cq W_qb, a head
              [qk_nope_head_dim] + [qk_rope_head_dim]
        [ckv | k_rope] = a W_kva  [kv_lora_rank] + [qk_rope_head_dim];
        ckv = Nkv(ckv);  [k_nope | v] = ckv W_kvb, a head
              [qk_nope_head_dim] + [v_head_dim];  k_rope ONE vector a token,
              shared by all heads
        rotate-half RoPE on q_rope, k_rope [rope_theta], YaRN frequencies
              [rope_scaling]; cos and sin times m(mscale) / m(mscale_all_dim),
              m(s) = 0.1 s ln(factor) + 1
        o = causal softmax([q_nope | q_rope] [k_nope | k_rope]^T
              (d_nope + d_rope)^-1/2 m(mscale_all_dim)^2) v
        out = concat(o) W_o                    no bias anywhere
    dense MLP: (silu(a Wg) * a Wu) Wd, width [intermediate_size]
    sparse MLP, on a = N2(h):
        s = sigmoid(a W_r) over all E experts, float32   [scoring_func]
        e = top-k(s + b) [num_experts_per_tok]: b (E,) moves the choice only
              [topk_method noaux_tc]; [n_group] = [topk_group] = 1: no groups
        w = s[e];  w <- w / sum(w) [norm_topk_prob];  w <- c w
              [routed_scaling_factor]
        routed = sum over the chosen e THAT ARE HELD of
                 w_e (silu(a Wg_e) * a Wu_e) Wd_e   width [moe_intermediate_size]
        out = routed + (silu(a Sg) * a Su) Sd       [n_shared_experts] x width
    RMSNorm: x / sqrt(mean(x^2) + rms_norm_eps) * gamma
    loss: mean cross-entropy of z against the next token

Departures from the published description, each under ``assumed`` in the
configuration file: RoPE rotates halves of de-interleaved pairs (the published
interleaved form under a fixed permutation of W_qb's and W_kva's rotary
columns); where ``hc_eps`` and the clip stand; columns before rows in an
iteration; streams start as copies and are summed at the end; one mixing a
sub-block; no multi-token-prediction module ([num_nextn_predict_layers] 0).

**The share.**  A configuration may hold part of every layer: experts
``first_expert .. first_expert + n_routed_experts - 1`` of ``router_experts``
(the router still scores all of them) and ``vocab_size`` ids.  What the absent
experts and ids would have added is left out here exactly as in the program.

Nothing here reads anything the program made: weights come from
:func:`init_params` (the benchmark's seed), and :func:`as_gpt2_custom` is the
one place that knows the program's parameter names (those of
``presets.xing_custom``; the function keeps the name ``kinds/train.py``
calls).  The router's selection bias is no weight: it is a constant of the
configuration (:func:`router_bias`), handed to the program's preset as the
buffer's first value.

Two things are done for room and change no arithmetic: every layer and the
head run under ``jax.checkpoint``, and the attention scores are taken a block
of query rows at a time (under ``lax.map``).

``dtype`` selects the precision everything is computed in, as in
``reference/gpt2.py``; the router's scores, the stream statistics and the
Sinkhorn iterations are float32 in every precision (matmul operands rounded
like any other's): the configuration states them so.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gpt2 import (PRECISIONS, _with_precision, seed_key,
                                      tree_rel_error)
from benchmark.reference.laguna import Weights, yarn_inv_freq

__all__ = ["dims", "init_params", "init_program_weights", "as_gpt2_custom",
           "loss_and_grad", "mean_loss_and_grad", "forward", "sinkhorn",
           "sinkhorn_err", "route", "router_bias", "softmax_scale",
           "tree_rel_error", "PRESET", "preset_args"]

QUERY_BLOCK = 512


def dims(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys (the
    published names; ``n_routed_experts`` is the experts *held*,
    ``router_experts`` the router's width, default the same)."""
    depth = int(cfg["num_hidden_layers"])
    dense = int(cfg["first_k_dense_replace"])
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise ValueError("group-limited routing is not written here")
    if cfg.get("scoring_func") != "sigmoid":
        raise ValueError("scoring_func must be 'sigmoid'")
    d_nope, d_rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    held = int(cfg["n_routed_experts"])
    return {"d": int(cfg["hidden_size"]), "depth": depth,
            "vocab": int(cfg["vocab_size"]),
            "block": int(cfg["max_position_embeddings"]),
            "heads": int(cfg["num_attention_heads"]),
            "q_rank": int(cfg["q_lora_rank"]),
            "kv_rank": int(cfg["kv_lora_rank"]),
            "d_nope": d_nope, "d_rope": d_rope,
            "head_dim": d_nope + d_rope, "d_v": int(cfg["v_head_dim"]),
            "mlp_types": tuple("dense" if i < dense else "sparse"
                               for i in range(depth)),
            "intermediate": int(cfg["intermediate_size"]),
            "experts": int(cfg.get("router_experts", held)),
            "held": held, "first": int(cfg.get("first_expert", 0)),
            "top_k": int(cfg["num_experts_per_tok"]),
            "moe_intermediate": int(cfg["moe_intermediate_size"]),
            "shared": (int(cfg["n_shared_experts"])
                       * int(cfg["moe_intermediate_size"])),
            "scale": float(cfg["routed_scaling_factor"]),
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "streams": int(cfg["hc_mult"]),
            "sinkhorn_iters": int(cfg["hc_sinkhorn_iters"]),
            "hc_eps": float(cfg["hc_eps"]),
            "clamp": (float(cfg["mhc_h_res_clamp_min"]),
                      float(cfg["mhc_h_res_clamp_max"])),
            "theta": float(cfg["rope_theta"]),
            "rope": tuple(sorted(cfg["rope_scaling"].items())),
            "eps": float(cfg["rms_norm_eps"]),
            "bias_update_rate": float(cfg.get("bias_update_rate", 0.001)),
            "published_layers": int(cfg.get("published", {}).get(
                "num_hidden_layers", depth))}


def router_bias(experts: int, layer: int) -> np.ndarray:
    """The selection bias a sparse layer starts from: N(0, 0.1), a constant
    of the layer's number (the bias is a buffer the balance rule moves, no
    weight: the program's preset takes it as the buffer's first value).
    Large enough beside sigmoid scores near 0.5 to move who is chosen."""
    rng = np.random.default_rng([0xB1A5, int(layer)])
    return (0.1 * rng.standard_normal(experts)).astype(np.float32)


def _mscale(factor: float, s: float) -> float:
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(d_qk: int, rope: dict) -> float:
    """``d_qk^-1/2 · m(mscale_all_dim)^2``: YaRN's factor carried by the
    scores' scale (as the published models of this attention compute it)."""
    m = _mscale(float(rope["factor"]), float(rope.get("mscale_all_dim", 0)))
    return d_qk ** -0.5 * m * m


def rope_amplitude(rope: dict) -> float:
    """What cos and sin are multiplied by: ``m(mscale) / m(mscale_all_dim)``."""
    factor = float(rope["factor"])
    return (_mscale(factor, float(rope.get("mscale", 1)))
            / _mscale(factor, float(rope.get("mscale_all_dim", 0))))


_HYPER = ("heads", "q_rank", "kv_rank", "d_nope", "d_rope", "d_v",
          "mlp_types", "first", "top_k", "scale", "norm_topk", "streams",
          "sinkhorn_iters", "hc_eps", "clamp", "theta", "rope", "eps",
          "bias")
_SIZES = ("d", "depth", "vocab", "heads", "q_rank", "kv_rank", "d_nope",
          "d_rope", "d_v", "mlp_types", "intermediate", "experts", "held",
          "moe_intermediate", "shared", "streams", "published_layers")


def _hyper(sizes: dict) -> tuple:
    bias = tuple(
        tuple(float(b) for b in router_bias(sizes["experts"], i))
        if kind == "sparse" else None
        for i, kind in enumerate(sizes["mlp_types"]))
    return tuple(sorted((k, {**sizes, "bias": bias}[k]) for k in _HYPER))


def _init_arrays(key, *, d, depth, vocab, heads, q_rank, kv_rank, d_nope,
                 d_rope, d_v, mlp_types, intermediate, experts, held,
                 moe_intermediate, shared, streams, published_layers):
    std = 0.02
    proj_std = std / (2 * published_layers) ** 0.5
    keys = iter(jax.random.split(key, 2 + 20 * depth))
    n = streams

    def normal(shape, s=std):
        return s * jax.random.normal(next(keys), shape, jnp.float32)

    def mixing():
        # the dynamic part (alpha * x~ Phi) and the static one (the bias)
        # of one order, so that a wrong gradient of either shows
        b_res = normal((n, n), 1.0) + 4.0 * jnp.eye(n)
        return {"phi": normal((n * d, 2 * n + n * n)),
                "alpha": jnp.ones((3,)),
                "bias": jnp.concatenate([normal((2 * n,), 1.0),
                                         b_res.reshape(-1)])}

    params = {"wte": normal((vocab, d)), "head": normal((d, vocab)),
              "nf": jnp.ones((d,))}
    for i in range(depth):
        layer = {"n1": jnp.ones((d,)), "n2": jnp.ones((d,)),
                 "hc_a": mixing(), "hc_m": mixing(),
                 "q_a": normal((d, q_rank)), "q_norm": jnp.ones((q_rank,)),
                 "q_b": normal((q_rank, heads * (d_nope + d_rope))),
                 "kv_a": normal((d, kv_rank + d_rope)),
                 "kv_norm": jnp.ones((kv_rank,)),
                 "kv_b": normal((kv_rank, heads * (d_nope + d_v))),
                 "o_w": normal((heads * d_v, d), proj_std)}
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
    depth), norm gains 1; the stream mixing as ``assumed`` states it (Phi
    N(0, 0.02), alpha 1, b_pre and b_post N(0, 1), b_res N(0, 1) + 4 I)."""
    sizes = dims(cfg)
    return Weights(_init(seed_key(seed), **{k: sizes[k] for k in _SIZES}),
                   _hyper(sizes))


def as_gpt2_custom(weights, depth: int) -> dict:
    """The same weights (or a gradient) under the names and layouts of the
    program's ``presets.xing_custom`` DSL (linear weights stored ``(out,
    in)``; W_qb's columns a head [nope | rope], W_kvb's a head [nope | v],
    heads contiguous; the expert stacks ``(held, out, in)``)."""
    params = weights.params if isinstance(weights, Weights) else weights
    out = {"layers.0.weight": params["wte"]}
    for i in range(depth):
        h = params[f"h{i}"]
        a, m = f"layers.{1 + i}.0", f"layers.{1 + i}.1"
        for p, hc in ((a, h["hc_a"]), (m, h["hc_m"])):
            out.update({f"{p}.phi.weight": hc["phi"].T,
                        f"{p}.alpha": hc["alpha"], f"{p}.bias": hc["bias"]})
        out.update({
            f"{a}.body.0.weight": h["n1"],
            f"{a}.body.1.q_a_proj.weight": h["q_a"].T,
            f"{a}.body.1.q_a_norm.weight": h["q_norm"],
            f"{a}.body.1.q_b_proj.weight": h["q_b"].T,
            f"{a}.body.1.kv_a_proj.weight": h["kv_a"].T,
            f"{a}.body.1.kv_a_norm.weight": h["kv_norm"],
            f"{a}.body.1.kv_b_proj.weight": h["kv_b"].T,
            f"{a}.body.1.o_proj.weight": h["o_w"].T,
            f"{m}.body.0.weight": h["n2"]})
        mlp = f"{m}.body.1"
        if "router" in h:
            swap = lambda t: jnp.swapaxes(t, 1, 2)
            out.update({
                f"{mlp}.router.weight": h["router"].T,
                f"{mlp}.experts.gate_proj.weight": swap(h["e_gate"]),
                f"{mlp}.experts.up_proj.weight": swap(h["e_up"]),
                f"{mlp}.experts.down_proj.weight": swap(h["e_down"]),
                f"{mlp}.shared_expert.gate_proj.weight": h["s_gate"].T,
                f"{mlp}.shared_expert.up_proj.weight": h["s_up"].T,
                f"{mlp}.shared_expert.down_proj.weight": h["s_down"].T})
        else:
            out.update({f"{mlp}.gate_proj.weight": h["gate_proj"].T,
                        f"{mlp}.up_proj.weight": h["up_proj"].T,
                        f"{mlp}.down_proj.weight": h["down_proj"].T})
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


def _rope(x, theta: float, rope: dict):
    """Rotate-half RoPE on the whole last axis of ``(B, H, T, D)`` with
    YaRN's blended frequencies; cos and sin times :func:`rope_amplitude`."""
    T, D = x.shape[-2:]
    inv = yarn_inv_freq(D, theta, float(rope["factor"]),
                        float(rope["original_max_position_embeddings"]),
                        float(rope["beta_fast"]), float(rope["beta_slow"]))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    amplitude = rope_amplitude(rope)
    cos = amplitude * jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = amplitude * jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    turned = jnp.concatenate([-x2, x1], -1)
    return (x.astype(jnp.float32) * cos
            + turned.astype(jnp.float32) * sin).astype(x.dtype)


def _attention(q, k, v, scale: float, mm):
    """Causal softmax(q k^T · scale) v with q, k ``(B, H, T, D)`` and v
    ``(B, H, T, Dv)``, a block of query rows at a time (``lax.map``); the
    softmax in float32."""
    T, D = q.shape[-2:]
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    cols = jnp.arange(T)
    keys_t = k.transpose(0, 1, 3, 2)

    def rows_of(args):
        start, queries = args                       # queries (B, H, block, D)
        rows = start + jnp.arange(block)
        s = mm(queries, keys_t).astype(jnp.float32) * scale
        s = jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1).astype(v.dtype), v)

    blocks = jnp.moveaxis(q.reshape(q.shape[:2] + (T // block, block, D)),
                          2, 0)
    out = jax.lax.map(rows_of, (jnp.arange(0, T, block), blocks))
    return jnp.moveaxis(out, 0, 2).reshape(q.shape[:3] + v.shape[-1:])


def _latent_attention(h, a, *, hyper, mm):
    """The expanded form: every head's keys and values made from the one
    normed latent vector a token, the rotary key shared by all heads."""
    B, T, _ = a.shape
    H, dn, dr, dv = (hyper[k] for k in ("heads", "d_nope", "d_rope", "d_v"))
    rope = dict(hyper["rope"])
    heads_first = lambda t: t.reshape(B, T, H, -1).transpose(0, 2, 1, 3)
    cq = _rmsnorm(mm(a, h["q_a"]), h["q_norm"], hyper["eps"])
    q = heads_first(mm(cq, h["q_b"]))
    latent = mm(a, h["kv_a"])
    ckv = _rmsnorm(latent[..., :hyper["kv_rank"]], h["kv_norm"],
                   hyper["eps"])
    kv = heads_first(mm(ckv, h["kv_b"]))
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_rope = _rope(q[..., dn:], hyper["theta"], rope)
    k_rope = _rope(latent[:, None, :, hyper["kv_rank"]:], hyper["theta"],
                   rope)                                    # (B, 1, T, dr)
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, H, T, dr))],
                        -1)
    o = _attention(q, k, v, softmax_scale(dn + dr, rope), mm)
    return mm(o.transpose(0, 2, 1, 3).reshape(B, T, H * dv), h["o_w"])


def _swiglu(a, gate, up, down, mm):
    return mm(jax.nn.silu(mm(a, gate)) * mm(a, up), down)


def route(a, router, bias, *, top_k: int, scale: float, norm_topk: bool,
          mm=jnp.matmul):
    """``(weights, experts)``, both ``(..., top_k)``: sigmoid scores over
    all experts in float32; the choice by score + bias; the weights the
    scores of the chosen alone (no bias), renormalised, times ``scale``."""
    f32 = lambda t: t.astype(jnp.float32)
    s = jax.nn.sigmoid(mm(f32(a), f32(router)))
    _, e = jax.lax.top_k(s + jnp.asarray(bias, jnp.float32), top_k)
    w = jnp.take_along_axis(s, e, axis=-1)
    if norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    return scale * w, e


def _sparse(h, a, bias, *, first, top_k, scale, norm_topk, mm):
    """routed (over the held experts) + shared; ``a`` ``(B, T, d)``."""
    w, e = route(a, h["router"], bias, top_k=top_k, scale=scale,
                 norm_topk=norm_topk, mm=mm)

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


def sinkhorn(logits, iters: int, eps: float):
    """``exp(logits)`` made doubly stochastic over its last two axes:
    ``iters`` times columns, then rows, each divided by its sum + ``eps``.
    Differentiated through every iteration."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
    return m


def _mixed(hc, X, f, *, hyper, mm):
    """One sub-block ``f`` under its stream mixing; ``X`` ``(B, T, n, d)``.
    Returns the new streams and the largest |column sum - 1| of H_res."""
    B, T, n, d = X.shape
    f32 = lambda t: t.astype(jnp.float32)
    xf = f32(X).reshape(B, T, n * d)
    xt = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                            + hyper["eps"])
    u = f32(mm(xt.astype(X.dtype), hc["phi"]))
    alpha, bias = f32(hc["alpha"]), f32(hc["bias"])
    pre = jax.nn.sigmoid(alpha[0] * u[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * u[..., n:2 * n] + bias[n:2 * n])
    lo, hi = hyper["clamp"]
    res = sinkhorn(jnp.clip(
        alpha[2] * u[..., 2 * n:].reshape(B, T, n, n)
        + bias[2 * n:].reshape(n, n), lo, hi),
        hyper["sinkhorn_iters"], hyper["hc_eps"])
    y = f(jnp.einsum("btn,btnd->btd", pre, f32(X)).astype(X.dtype))
    new = (jnp.einsum("btij,btjd->btid", res, f32(X))
           + post[..., None] * f32(y)[:, :, None, :])
    err = jnp.max(jnp.abs(jnp.sum(res, -2) - 1.0))
    return new.astype(X.dtype), err


def _layer(h, X, *, index, hyper, mm):
    eps = hyper["eps"]
    X, err_a = _mixed(
        h["hc_a"], X, lambda u: _latent_attention(
            h, _rmsnorm(u, h["n1"], eps), hyper=hyper, mm=mm),
        hyper=hyper, mm=mm)
    if "router" in h:
        mlp = lambda u: _sparse(
            h, _rmsnorm(u, h["n2"], eps), hyper["bias"][index],
            first=hyper["first"], top_k=hyper["top_k"],
            scale=hyper["scale"], norm_topk=hyper["norm_topk"], mm=mm)
    else:
        mlp = lambda u: _swiglu(_rmsnorm(u, h["n2"], eps), h["gate_proj"],
                                h["up_proj"], h["down_proj"], mm)
    X, err_m = _mixed(h["hc_m"], X, mlp, hyper=hyper, mm=mm)
    return X, jnp.maximum(err_a, err_m)


def _hidden(weights: Weights, tokens, precision: str):
    """The final-normed activations, the matmul in use and the largest
    Sinkhorn column error of the call."""
    hyper = dict(weights.hyper)
    dtype, rnd = PRECISIONS[precision]
    mm = (jnp.matmul if rnd is None
          else lambda a, b: jnp.matmul(rnd(a), rnd(b)))
    params = jax.tree.map(lambda a: a.astype(dtype), weights.params)
    u = params["wte"][tokens]
    X = jnp.broadcast_to(u[:, :, None, :],
                         u.shape[:2] + (hyper["streams"], u.shape[-1]))
    worst = jnp.zeros((), jnp.float32)
    for i in range(len(hyper["mlp_types"])):
        layer = jax.checkpoint(functools.partial(
            _layer, index=i, hyper=hyper, mm=mm))
        X, err = layer(params[f"h{i}"], X)
        worst = jnp.maximum(worst, err)
    u = jnp.sum(X.astype(jnp.float32), axis=2).astype(X.dtype)
    return params, _rmsnorm(u, params["nf"], hyper["eps"]), mm, worst


def _head_loss(head, h, y, *, mm):
    logp = jax.nn.log_softmax(mm(h, head).astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


def _loss(weights: Weights, x, y, precision):
    params, h, mm, _ = _hidden(weights, x, precision)
    return jax.checkpoint(functools.partial(_head_loss, mm=mm))(
        params["head"], h, y)


def _check_heads(weights, heads):
    if heads != dict(weights.hyper)["heads"]:
        raise ValueError(f"heads={heads} is not the configuration's")


@_with_precision
@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def forward(weights, tokens, *, heads: int, precision: str = "float32"):
    """``(B, T, vocab)`` logits of the full causal forward, float32."""
    _check_heads(weights, heads)
    params, h, mm, _ = _hidden(weights, tokens, precision)
    return mm(h, params["head"]).astype(jnp.float32)


@_with_precision
@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def sinkhorn_err(weights, tokens, *, heads: int, precision: str = "float32"):
    """The largest |column sum - 1| of H_res after its last iteration, over
    the call's tokens and sub-blocks (what the program's ``hc_sinkhorn_err``
    counter reads)."""
    _check_heads(weights, heads)
    return _hidden(weights, tokens, precision)[3]


@_with_precision
@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def loss_and_grad(weights, x, y, *, heads: int, precision: str = "float32"):
    """(loss, gradient in float32 as :class:`Weights`) of the mean
    cross-entropy of ``x`` (B, T) against ``y`` (B, T)."""
    _check_heads(weights, heads)
    value, grads = jax.value_and_grad(_loss)(weights, x, y, precision)
    return value, jax.tree.map(lambda g: g.astype(jnp.float32), grads)


_add_into = jax.jit(lambda acc, g: jax.tree.map(jnp.add, acc, g),
                    donate_argnums=(0,))


def mean_loss_and_grad(weights, xs, ys, *, heads: int, rows: int,
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

PRESET = "xing_custom"


def preset_args(cfg: dict) -> dict:
    """Arguments of ``penroz_tpu.models.presets.xing_custom`` for ``cfg``."""
    s = dims(cfg)
    return {"d": s["d"], "heads": s["heads"], "q_rank": s["q_rank"],
            "kv_rank": s["kv_rank"], "d_nope": s["d_nope"],
            "d_rope": s["d_rope"], "d_v": s["d_v"],
            "mlp_layer_types": list(s["mlp_types"]),
            "intermediate": s["intermediate"], "num_experts": s["experts"],
            "experts_held": s["held"], "first_expert": s["first"],
            "top_k": s["top_k"], "moe_intermediate": s["moe_intermediate"],
            "shared_intermediate": s["shared"], "vocab": s["vocab"],
            "rope_theta": s["theta"], "rope_scaling": dict(s["rope"]),
            "routed_scale": s["scale"], "norm_topk": s["norm_topk"],
            "streams": s["streams"], "sinkhorn_iters": s["sinkhorn_iters"],
            "hc_eps": s["hc_eps"], "res_clamp": list(s["clamp"]),
            "bias_update_rate": s["bias_update_rate"],
            "router_bias": [
                [float(b) for b in router_bias(s["experts"], i)]
                for i, kind in enumerate(s["mlp_types"]) if kind == "sparse"],
            "eps": s["eps"], "published_layers": s["published_layers"]}
