"""Plain Nemotron-H (a hybrid language model: Mamba-2 state-space mixers,
LatentMoE expert layers and a few attention layers, one mixer a layer) in
``jax.numpy``, whole or as one rank's share: weights from a seed, forward,
loss, gradients.

The yardstick for the ``nemotron_h`` configurations of the benchmark (NVIDIA,
``https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``
``config.json``; the SSD form is arXiv:2405.21060's).  No kernels, no sort, no
chunks: the state-space recurrence is written token by token (``lax.scan``
over the sequence, the ``(P, N)`` state a head carried), the convolution is
four shifted copies, attention takes the whole score matrix of a block of
query rows, the routed experts are a loop over the experts held, each
computed for every token and weighted by what the router gave it.  Config
keys in brackets; what the config does not say is listed in the configuration
file under ``assumed``::

    x = E[tokens]
    for l, kind in enumerate([hybrid_override_pattern]):
        x = x + mixer_l(N_l(x))                one mixer a layer
    z = Nf(x) W_head                           logits, head untied

    M, on u = N(x): H heads [mamba_num_heads] of P [mamba_head_dim],
       G groups [n_groups], state N [ssm_state_size], d_in = H P:
        [z | xBC | dt] = u W_in                d_in | d_in + 2 G N | H
        xBC = silu(conv(xBC))                  depthwise, causal, [conv_kernel]
                                               taps and a bias a channel
        [x | B | C] = xBC                      d_in | G N | G N
        D_t,h = softplus(dt_t,h + dt_bias_h);  A_h = -exp(A_log_h)
        S_t,h = exp(D_t,h A_h) S_t-1,h + D_t,h x_t,h (x) B_t,g(h)
        y_t,h = S_t,h C_t,g(h) + D_h x_t,h     g(h) = h // (H / G)
        y = GroupRMSNorm(y silu(z))            G groups, gain d_in wide,
                                               [layer_norm_epsilon]
        out = y W_out
    E, on u = N(x):
        s = sigmoid(u W_r) over all experts [n_routed_experts], float32
        e = top-k(s + b) [num_experts_per_tok]; b moves the choice only
        w = s[e]; w <- w / sum(w) [norm_topk_prob]; w <- c w
            [routed_scaling_factor]
        l = u W_down                           [moe_latent_size]
        r = sum over the chosen e THAT ARE HELD of w_e relu(l W1_e)^2 W2_e
            width [moe_intermediate_size], not gated [mlp_hidden_act relu2]
        out = r W_up + relu(u V1)^2 V2         shared expert
            [moe_shared_expert_intermediate_size], at the full width
    *, on u = N(x): causal softmax(q k^T [head_dim]^-1/2) v, [num_attention_heads]
        query heads on [num_key_value_heads] key/value heads, no rotary
        embedding, no bias
    RMSNorm: x / sqrt(mean(x^2) + [norm_eps]) * gamma
    loss: mean cross-entropy of z against the next token

**The share.**  A configuration may hold part of every layer: Mamba heads
``0 .. mamba_num_heads - 1`` of ``published.mamba_num_heads`` in whole groups
(every parameter cut to them: the gated norm is a group's), experts
``first_expert .. first_expert + n_routed_experts - 1`` of ``router_experts``
(the router still scores all of them), the attention heads and ``vocab_size``
ids it names.  What the absent heads, experts and ids would have added is
left out here exactly as in the program.

Nothing here reads anything the program made: weights come from
:func:`init_params` (the benchmark's seed), and :func:`as_gpt2_custom` is the
one place that knows the program's parameter names (those of
``presets.nemotron_h_custom``; the function keeps the name ``kinds/train.py``
calls).  The router's selection bias is no weight: it is a constant of the
configuration (:func:`router_bias`), handed to the program's preset as the
buffer's first value.

Done for room, changing no arithmetic: every layer and the head run under
``jax.checkpoint``, and the attention scores are taken a block of query rows
at a time.

``dtype`` selects the precision everything is computed in, as in
``reference/gpt2.py``; the router's scores and the recurrence's statistics
(the step size, the decays, the state) are float32 in every precision (matmul
operands rounded like any other's): the configuration states them so.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import (PRECISIONS, _with_precision, seed_key,
                                      tree_rel_error)
from benchmark.reference.laguna import Weights
from benchmark.reference.xing import (_attention, _head_loss, _rmsnorm,
                                      route, router_bias)

__all__ = ["dims", "init_params", "init_program_weights", "as_gpt2_custom",
           "loss_and_grad", "mean_loss_and_grad", "forward", "mamba_mixer",
           "latent_moe", "ssd_recurrence", "route", "router_bias",
           "tree_rel_error", "PRESET", "preset_args"]


STRETCH = 64        # tokens the recurrence's backward holds states for


def dims(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys (the
    published names; the head, expert and vocabulary counts are those
    *held*, ``router_experts`` the router's width, ``published`` the whole
    model's where the share needs them)."""
    pattern = str(cfg["hybrid_override_pattern"])
    if len(pattern) != int(cfg["num_hidden_layers"]) \
            or set(pattern) - set("ME*"):
        raise ValueError("hybrid_override_pattern names M, E or * for each "
                         "of num_hidden_layers layers")
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise ValueError("group-limited routing is not written here")
    if cfg.get("mlp_hidden_act") != "relu2":
        raise ValueError("mlp_hidden_act must be 'relu2'")
    published = cfg.get("published", {})
    held = int(cfg["n_routed_experts"])
    heads = int(cfg["mamba_num_heads"])
    all_heads = int(published.get("mamba_num_heads", heads))
    all_groups = int(published.get("n_groups", cfg["n_groups"]))
    if heads * all_groups != int(cfg["n_groups"]) * all_heads:
        raise ValueError("the Mamba heads held are not whole groups")
    return {"d": int(cfg["hidden_size"]), "depth": len(pattern),
            "pattern": pattern, "vocab": int(cfg["vocab_size"]),
            "block": int(cfg["max_position_embeddings"]),
            "m_heads": heads, "m_all_heads": all_heads,
            "m_head_dim": int(cfg["mamba_head_dim"]),
            "groups": int(cfg["n_groups"]),
            "state": int(cfg["ssm_state_size"]),
            "conv": int(cfg["conv_kernel"]),
            "chunk": int(cfg["chunk_size"]),
            "heads": int(cfg["num_attention_heads"]),
            "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg["head_dim"]),
            "experts": int(cfg.get("router_experts", held)),
            "held": held, "first": int(cfg.get("first_expert", 0)),
            "top_k": int(cfg["num_experts_per_tok"]),
            "moe_intermediate": int(cfg["moe_intermediate_size"]),
            "latent": int(cfg["moe_latent_size"]),
            "shared": int(cfg["moe_shared_expert_intermediate_size"]),
            "scale": float(cfg["routed_scaling_factor"]),
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "eps": float(cfg["norm_eps"]),
            "dt_range": (float(cfg["time_step_min"]),
                         float(cfg["time_step_max"]),
                         float(cfg["time_step_floor"])),
            "bias_update_rate": float(cfg.get("bias_update_rate", 0.001)),
            "published_layers": int(published.get("num_hidden_layers",
                                                  len(pattern)))}


_HYPER = ("pattern", "m_heads", "m_head_dim", "groups", "state", "conv",
          "heads", "kv_heads", "head_dim", "first", "top_k", "scale",
          "norm_topk", "eps", "bias")
_SIZES = ("d", "pattern", "vocab", "m_heads", "m_head_dim", "groups", "state",
          "conv", "heads", "kv_heads", "head_dim", "experts", "held",
          "moe_intermediate", "latent", "shared", "dt_range",
          "published_layers")


def _hyper(sizes: dict) -> tuple:
    bias = tuple(
        tuple(float(b) for b in router_bias(sizes["experts"], i))
        if kind == "E" else None for i, kind in enumerate(sizes["pattern"]))
    return tuple(sorted((k, {**sizes, "bias": bias}[k]) for k in _HYPER))


def _init_arrays(key, *, d, pattern, vocab, m_heads, m_head_dim, groups,
                 state, conv, heads, kv_heads, head_dim, experts, held,
                 moe_intermediate, latent, shared, dt_range,
                 published_layers):
    std = 0.02
    proj_std = std / (2 * published_layers) ** 0.5
    keys = iter(jax.random.split(key, 2 + 12 * len(pattern)))
    d_in, bc = m_heads * m_head_dim, groups * state

    def normal(shape, s=std):
        return s * jax.random.normal(next(keys), shape, jnp.float32)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    params = {"wte": normal((vocab, d)), "head": normal((d, vocab)),
              "nf": jnp.ones((d,))}
    for i, kind in enumerate(pattern):
        layer = {"n": jnp.ones((d,))}
        if kind == "M":
            lo, hi, floor = dt_range
            dt = jnp.maximum(jnp.exp(uniform((m_heads,), math.log(lo),
                                             math.log(hi))), floor)
            bound = conv ** -0.5
            layer.update(
                w_in=normal((d, 2 * d_in + 2 * bc + m_heads)),
                conv_w=uniform((conv, d_in + 2 * bc), -bound, bound),
                conv_b=uniform((d_in + 2 * bc,), -bound, bound),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                a_log=jnp.log(uniform((m_heads,), 1.0, 16.0)),
                skip=jnp.ones((m_heads,)), gain=jnp.ones((d_in,)),
                w_out=normal((d_in, d), proj_std))
        elif kind == "E":
            layer.update(
                router=normal((d, experts)),
                down=normal((d, latent)), up=normal((latent, d), proj_std),
                e_w1=normal((held, latent, moe_intermediate)),
                e_w2=normal((held, moe_intermediate, latent), proj_std),
                s_w1=normal((d, shared)),
                s_w2=normal((shared, d), proj_std))
        else:
            layer.update(
                qkv=normal((d, (heads + 2 * kv_heads) * head_dim)),
                o_w=normal((heads * head_dim, d), proj_std))
        params[f"h{i}"] = layer
    return params


@functools.partial(jax.jit, static_argnames=_SIZES)
def _init(key, **sizes):
    return _init_arrays(key, **sizes)


def init_params(cfg: dict, seed: int) -> Weights:
    """Every weight held, in float32, made on the default device in one
    jitted call: N(0, 0.02), every projection onto the residual path (the
    mixer's and attention's output, the experts' and the shared expert's
    second matrix, the latent's way back) scaled by 1/sqrt(2 · the published
    depth); the convolution U(±1/sqrt(taps)); ``A_log = log U(1, 16)``;
    ``dt_bias`` the inverse softplus of a log-uniform draw in
    [``time_step_min``, ``time_step_max``] floored at ``time_step_floor``;
    D and the gains 1."""
    sizes = dims(cfg)
    return Weights(_init(seed_key(seed), **{k: sizes[k] for k in _SIZES}),
                   _hyper(sizes))


def as_gpt2_custom(weights, depth: int) -> dict:
    """The same weights (or a gradient) under the names and layouts of the
    program's ``presets.nemotron_h_custom`` DSL (linear weights stored
    ``(out, in)``; the convolution ``(channels, taps)``; the expert stacks
    ``(held, out, in)``)."""
    params = weights.params if isinstance(weights, Weights) else weights
    out = {"layers.0.weight": params["wte"]}
    for i in range(depth):
        h, p = params[f"h{i}"], f"layers.{1 + i}"
        out[f"{p}.norm.weight"] = h["n"]
        m = f"{p}.mixer"
        if "w_in" in h:
            out.update({
                f"{m}.in_proj.weight": h["w_in"].T,
                f"{m}.conv1d.weight": h["conv_w"].T,
                f"{m}.conv1d.bias": h["conv_b"],
                f"{m}.dt_bias": h["dt_bias"], f"{m}.A_log": h["a_log"],
                f"{m}.D": h["skip"], f"{m}.norm.weight": h["gain"],
                f"{m}.out_proj.weight": h["w_out"].T})
        elif "router" in h:
            swap = lambda t: jnp.swapaxes(t, 1, 2)
            out.update({
                f"{m}.router.weight": h["router"].T,
                f"{m}.experts.up_proj.weight": swap(h["e_w1"]),
                f"{m}.experts.down_proj.weight": swap(h["e_w2"]),
                f"{m}.latent_down.weight": h["down"].T,
                f"{m}.latent_up.weight": h["up"].T,
                f"{m}.shared_expert.up_proj.weight": h["s_w1"].T,
                f"{m}.shared_expert.down_proj.weight": h["s_w2"].T})
        else:
            out.update({f"{m}.0.weight": h["qkv"].T,
                        f"{m}.2.weight": h["o_w"].T})
    out.update({f"layers.{depth + 1}.weight": params["nf"],
                f"layers.{depth + 2}.weight": params["head"].T})
    return out


@functools.partial(jax.jit, static_argnames=_SIZES)
def _init_for_program(key, **sizes):
    return as_gpt2_custom(_init_arrays(key, **sizes), len(sizes["pattern"]))


def init_program_weights(cfg: dict, seed: int) -> dict:
    """:func:`init_params` under the program's names, made in the same one
    jitted call (the reference's own layout is never held beside it)."""
    sizes = dims(cfg)
    return _init_for_program(seed_key(seed), **{k: sizes[k] for k in _SIZES})


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def ssd_recurrence(x, dt, A, Bm, Cm):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
    B_t``, token by token in float32: ``x (B, T, H, P)``, ``dt (B, T, H)``,
    ``A (H,)``, ``Bm``/``Cm (B, T, G, N)``, head ``h`` reading group
    ``h // (H / G)``."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    f32 = lambda t: t.astype(jnp.float32)
    spread = lambda t: jnp.repeat(f32(t), H // G, axis=2)     # (B, T, H, N)

    def token(S, at):
        x_t, dt_t, b_t, c_t = at
        decay = jnp.exp(dt_t * A)                              # (B, H)
        S = decay[..., None, None] * S + jnp.einsum(
            "bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t)
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t)

    # for room alone: the tokens walked a stretch at a time, each stretch
    # under jax.checkpoint, so that the backward holds a state a stretch
    # and one stretch's states a token, not a state a token of the sequence
    stretch = STRETCH if T % STRETCH == 0 else T
    over_time = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        (T // stretch, stretch) + t.shape[:1] + t.shape[2:])

    @jax.checkpoint
    def tokens_of(S, part):
        return jax.lax.scan(token, S, part)

    _, ys = jax.lax.scan(
        tokens_of, jnp.zeros((B, H, P, N), jnp.float32),
        (over_time(f32(x)), over_time(f32(dt)), over_time(spread(Bm)),
         over_time(spread(Cm))))
    return jnp.moveaxis(ys.reshape(T, B, H, P), 0, 1)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def mamba_mixer(h, u, *, heads, head_dim, groups, state, conv, eps, mm):
    """One M layer's mixer on the normed ``u (B, T, d)``."""
    B, T, _ = u.shape
    d_in, bc = heads * head_dim, groups * state
    f32 = lambda t: t.astype(jnp.float32)
    proj = mm(u, h["w_in"])
    z, xbc, dt = (proj[..., :d_in], proj[..., d_in:2 * d_in + 2 * bc],
                  proj[..., 2 * d_in + 2 * bc:])
    # the convolution: tap k reads the token (conv - 1 - k) before
    padded = jnp.pad(f32(xbc), ((0, 0), (conv - 1, 0), (0, 0)))
    mixed = f32(h["conv_b"])
    for k in range(conv):
        mixed = mixed + padded[:, k:k + T] * f32(h["conv_w"])[k]
    xbc = _silu(mixed).astype(u.dtype)
    x = xbc[..., :d_in].reshape(B, T, heads, head_dim)
    Bm = xbc[..., d_in:d_in + bc].reshape(B, T, groups, state)
    Cm = xbc[..., d_in + bc:].reshape(B, T, groups, state)
    step = jax.nn.softplus(f32(dt) + f32(h["dt_bias"]))
    y = ssd_recurrence(x, step, -jnp.exp(f32(h["a_log"])), Bm, Cm)
    y = y + f32(h["skip"])[:, None] * f32(x)
    y = y.reshape(B, T, d_in) * _silu(f32(z))
    per_group = y.reshape(B, T, groups, d_in // groups)
    per_group = per_group * jax.lax.rsqrt(
        jnp.mean(per_group * per_group, -1, keepdims=True) + eps)
    y = (per_group.reshape(B, T, d_in) * f32(h["gain"])).astype(u.dtype)
    return mm(y, h["w_out"])


def _relu2(a, w1, w2, mm):
    return mm(jnp.square(jax.nn.relu(mm(a, w1))), w2)


def latent_moe(h, u, bias, *, first, top_k, scale, norm_topk, mm):
    """One E layer's mixer on the normed ``u (B, T, d)``: routed (over the
    held experts, in the latent) + shared (at the full width)."""
    w, e = route(u, h["router"], bias, top_k=top_k, scale=scale,
                 norm_topk=norm_topk, mm=mm)
    low = mm(u, h["down"])

    def add(out, expert):                       # one of the experts held
        j, w1, w2 = expert
        share = jnp.sum(jnp.where(e == first + j, w, 0.0), -1)   # (B, T)
        y = _relu2(low, w1, w2, mm)
        return out + share[..., None] * y.astype(jnp.float32), None

    routed, _ = jax.lax.scan(
        add, jnp.zeros(low.shape, jnp.float32),
        (jnp.arange(h["e_w1"].shape[0]), h["e_w1"], h["e_w2"]))
    return (mm(routed.astype(u.dtype), h["up"])
            + _relu2(u, h["s_w1"], h["s_w2"], mm))


def _gqa(h, u, *, heads, kv_heads, head_dim, mm):
    B, T, _ = u.shape
    qkv = mm(u, h["qkv"])
    split = lambda t, n: t.reshape(B, T, n, head_dim).transpose(0, 2, 1, 3)
    q = split(qkv[..., :heads * head_dim], heads)
    k = split(qkv[..., heads * head_dim:(heads + kv_heads) * head_dim],
              kv_heads)
    v = split(qkv[..., (heads + kv_heads) * head_dim:], kv_heads)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    o = _attention(q, k, v, head_dim ** -0.5, mm)
    return mm(o.transpose(0, 2, 1, 3).reshape(B, T, heads * head_dim),
              h["o_w"])


def _layer(h, x, *, index, hyper, mm):
    u = _rmsnorm(x, h["n"], hyper["eps"])
    kind = hyper["pattern"][index]
    if kind == "M":
        y = mamba_mixer(h, u, heads=hyper["m_heads"],
                        head_dim=hyper["m_head_dim"], groups=hyper["groups"],
                        state=hyper["state"], conv=hyper["conv"],
                        eps=hyper["eps"], mm=mm)
    elif kind == "E":
        y = latent_moe(h, u, hyper["bias"][index], first=hyper["first"],
                       top_k=hyper["top_k"], scale=hyper["scale"],
                       norm_topk=hyper["norm_topk"], mm=mm)
    else:
        y = _gqa(h, u, heads=hyper["heads"], kv_heads=hyper["kv_heads"],
                 head_dim=hyper["head_dim"], mm=mm)
    return x + y


def _hidden(weights: Weights, tokens, precision: str):
    """The final-normed activations and the matmul in use."""
    hyper = dict(weights.hyper)
    dtype, rnd = PRECISIONS[precision]
    mm = (jnp.matmul if rnd is None
          else lambda a, b: jnp.matmul(rnd(a), rnd(b)))
    params = jax.tree.map(lambda a: a.astype(dtype), weights.params)
    x = params["wte"][tokens]
    for i in range(len(hyper["pattern"])):
        x = jax.checkpoint(functools.partial(
            _layer, index=i, hyper=hyper, mm=mm))(params[f"h{i}"], x)
    return params, _rmsnorm(x, params["nf"], hyper["eps"]), mm


def _loss(weights: Weights, x, y, precision):
    params, h, mm = _hidden(weights, x, precision)
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
    params, h, mm = _hidden(weights, tokens, precision)
    return mm(h, params["head"]).astype(jnp.float32)


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

PRESET = "nemotron_h_custom"


def preset_args(cfg: dict) -> dict:
    """Arguments of ``penroz_tpu.models.presets.nemotron_h_custom`` for
    ``cfg``."""
    s = dims(cfg)
    lo, hi, floor = s["dt_range"]
    return {"d": s["d"], "pattern": s["pattern"], "vocab": s["vocab"],
            "mamba_heads": s["m_all_heads"],
            "mamba_heads_held": s["m_heads"],
            "mamba_head_dim": s["m_head_dim"],
            "n_groups": s["groups"] * s["m_all_heads"] // s["m_heads"],
            "state_size": s["state"], "conv_kernel": s["conv"],
            "chunk_size": s["chunk"], "heads": s["heads"],
            "kv_heads": s["kv_heads"], "head_dim": s["head_dim"],
            "num_experts": s["experts"], "experts_held": s["held"],
            "first_expert": s["first"], "top_k": s["top_k"],
            "moe_intermediate": s["moe_intermediate"],
            "latent": s["latent"], "shared_intermediate": s["shared"],
            "routed_scale": s["scale"], "norm_topk": s["norm_topk"],
            "bias_update_rate": s["bias_update_rate"],
            "router_bias": [
                [float(b) for b in router_bias(s["experts"], i)]
                for i, kind in enumerate(s["pattern"]) if kind == "E"],
            "eps": s["eps"], "dt_min": lo, "dt_max": hi, "dt_floor": floor,
            "published_layers": s["published_layers"]}
