"""Plain GPT-2 in ``jax.numpy``: weights from a seed, forward, loss, gradients.

The yardstick for every GPT-2 configuration of the benchmark.  No kernels,
no cache, no batching tricks: token + learned position embeddings, pre-norm
blocks (LayerNorm eps 1e-5, fused QKV split [q|k|v] with heads contiguous,
causal softmax(QK^T / sqrt(d_head)) V, tanh-GELU MLP of width 4d), a final
LayerNorm and a linear head — Radford et al. 2019 as published in
``openai-community/gpt2*`` ``config.json``.

Departure from the published model, stated once: the output head is its own
matrix (not tied to ``wte``), because ``presets.gpt2_custom`` — the
architecture the configurations run — does not tie them.

Nothing here reads anything the program made: weights come from
:func:`init_params` (the benchmark's seed), and :func:`as_gpt2_custom` is the
one place that knows the program's parameter names, so that the program can
be handed these weights.

``dtype`` selects the precision everything is computed in.  float32 runs
under ``jax.default_matmul_precision("highest")`` (a TPU otherwise multiplies
float32 in bfloat16 passes); bfloat16 and the fp8 emulation are the *controls*
of the comparison that decides ``correct`` — the reference put in the
program's place one precision below what a configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


def dims(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys
    (Hugging Face GPT-2 names)."""
    return {"d": int(cfg["n_embd"]), "heads": int(cfg["n_head"]),
            "depth": int(cfg["n_layer"]), "vocab": int(cfg["vocab_size"]),
            "block": int(cfg["n_positions"])}


def seed_key(seed: int):
    """A PRNG key for any whole number ``seed`` (the driver's are above
    2**31, which a 32-bit key constructor refuses)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed >> 31), seed & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("d", "heads", "depth", "vocab",
                                             "block"))
def _init(key, *, d, heads, depth, vocab, block):
    del heads
    std, proj_std = 0.02, 0.02 / (2 * depth) ** 0.5
    keys = iter(jax.random.split(key, 3 + 4 * depth))

    def normal(shape, s):
        return s * jax.random.normal(next(keys), shape, jnp.float32)

    params = {"wte": normal((vocab, d), std), "wpe": normal((block, d), std),
              "head": normal((d, vocab), std),
              "lnf_g": jnp.ones((d,)), "lnf_b": jnp.zeros((d,))}
    for i in range(depth):
        params[f"h{i}"] = {
            "ln1_g": jnp.ones((d,)), "ln1_b": jnp.zeros((d,)),
            "qkv_w": normal((d, 3 * d), std), "qkv_b": jnp.zeros((3 * d,)),
            "proj_w": normal((d, d), proj_std), "proj_b": jnp.zeros((d,)),
            "ln2_g": jnp.ones((d,)), "ln2_b": jnp.zeros((d,)),
            "fc_w": normal((d, 4 * d), std), "fc_b": jnp.zeros((4 * d,)),
            "out_w": normal((4 * d, d), proj_std), "out_b": jnp.zeros((d,)),
        }
    return params


def init_params(cfg: dict, seed: int) -> dict:
    """Every weight of the model in float32, made on the default device in
    one jitted call.  GPT-2's initialisation: N(0, 0.02), residual
    projections scaled by 1/sqrt(2·depth), biases 0, LayerNorm gains 1."""
    return _init(seed_key(seed), **dims(cfg))


def as_gpt2_custom(params: dict, depth: int) -> dict:
    """The same weights under the names and layouts of the program's
    ``presets.gpt2_custom`` DSL (``layers.<i>…``, linear weights stored
    ``(out, in)``)."""
    out = {"layers.0.0.weight": params["wte"],
           "layers.0.1.weight": params["wpe"]}
    for i in range(depth):
        h, p = params[f"h{i}"], f"layers.{2 + i}"
        out.update({
            f"{p}.0.0.weight": h["ln1_g"], f"{p}.0.0.bias": h["ln1_b"],
            f"{p}.0.1.weight": h["qkv_w"].T, f"{p}.0.1.bias": h["qkv_b"],
            f"{p}.0.3.weight": h["proj_w"].T, f"{p}.0.3.bias": h["proj_b"],
            f"{p}.1.0.weight": h["ln2_g"], f"{p}.1.0.bias": h["ln2_b"],
            f"{p}.1.1.weight": h["fc_w"].T, f"{p}.1.1.bias": h["fc_b"],
            f"{p}.1.3.weight": h["out_w"].T, f"{p}.1.3.bias": h["out_b"],
        })
    out[f"layers.{2 + depth}.weight"] = params["lnf_g"]
    out[f"layers.{2 + depth}.bias"] = params["lnf_b"]
    out[f"layers.{3 + depth}.weight"] = params["head"].T
    return out


@functools.partial(jax.jit, static_argnames=("d", "heads", "depth", "vocab",
                                             "block"))
def _init_for_program(key, **sizes):
    return as_gpt2_custom(_init.__wrapped__(key, **sizes), sizes["depth"])


def init_program_weights(cfg: dict, seed: int) -> dict:
    """:func:`init_params` under the program's names, made in the same one
    jitted call (the reference's own layout is never held beside it: a
    GPT-2-large has no room for both next to its AdamW state)."""
    return _init_for_program(seed_key(seed), **dims(cfg))


# ---------------------------------------------------------------------------
# precision
# ---------------------------------------------------------------------------

def _fp8(x):
    """Round to float8 e4m3 (3 mantissa bits, exponents down to 2**-6,
    largest 448) with one scale per tensor (amax → 448) and come back: what
    a scaled fp8 matmul sees of its operand.  The rounding is spelled out in
    float32 arithmetic, because a TPU without fp8 units compiles a
    convert-to-fp8-and-back pair away (the first control on the chip read
    exactly the bfloat16 error, my chip run, PR 24).  Straight-through
    gradient."""
    xf = x.astype(jnp.float32)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30)
    y = xf * scale
    exponent = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    step = jnp.exp2(exponent - 3.0)
    q = (jnp.clip(jnp.round(y / step) * step, -448.0, 448.0)
         / scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(q - x)


PRECISIONS = {
    # name: (storage/compute dtype, operand rounding before each matmul)
    "float32": (jnp.float32, None),
    # float32 kept, multiplied at the backend's default precision: on a TPU
    # one bfloat16 pass with float32 accumulation, what the program's own
    # matmuls do.  No control: a witness of where the program's rounding is
    "float32_default": (jnp.float32, None),
    "bfloat16": (jnp.bfloat16, None),
    "fp8": (jnp.bfloat16, _fp8),
}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _layernorm(x, g, b):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _forward(params, tokens, heads: int, precision: str):
    dtype, rnd = PRECISIONS[precision]
    cast = lambda a: a.astype(dtype)
    mm = (jnp.matmul if rnd is None
          else lambda a, b: jnp.matmul(rnd(a), rnd(b)))
    B, T = tokens.shape
    x = cast(params["wte"])[tokens] + cast(params["wpe"])[:T]
    d = x.shape[-1]
    dh = d // heads
    causal = jnp.tril(jnp.ones((T, T), bool))
    depth = sum(k[0] == "h" and k[1:].isdigit() for k in params)
    for i in range(depth):
        h = {k: cast(v) for k, v in params[f"h{i}"].items()}
        a = _layernorm(x, h["ln1_g"], h["ln1_b"])
        qkv = mm(a, h["qkv_w"]) + h["qkv_b"]
        q, k, v = (t.reshape(B, T, heads, dh).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        s = mm(q, k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.asarray(dh, dtype))
        s = jnp.where(causal, s, jnp.asarray(-jnp.inf, dtype))
        p = jax.nn.softmax(s, axis=-1)
        o = mm(p, v).transpose(0, 2, 1, 3).reshape(B, T, d)
        x = x + mm(o, h["proj_w"]) + h["proj_b"]
        a = _layernorm(x, h["ln2_g"], h["ln2_b"])
        m = jax.nn.gelu(mm(a, h["fc_w"]) + h["fc_b"], approximate=True)
        x = x + mm(m, h["out_w"]) + h["out_b"]
    x = _layernorm(x, cast(params["lnf_g"]), cast(params["lnf_b"]))
    return mm(x, cast(params["head"]))


def _with_precision(fn):
    @functools.wraps(fn)
    def wrapped(*args, precision="float32", **kw):
        level = "highest" if precision == "float32" else "default"
        with jax.default_matmul_precision(level):
            return fn(*args, precision=precision, **kw)
    return wrapped


@_with_precision
@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def logits(params, tokens, *, heads: int, precision: str = "float32"):
    """``(B, T, vocab)`` logits of the full causal forward, in float32."""
    return _forward(params, tokens, heads, precision).astype(jnp.float32)


def _loss(params, x, y, heads, precision):
    z = _forward(params, x, heads, precision).astype(jnp.float32)
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


@_with_precision
@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def loss(params, x, y, *, heads: int, precision: str = "float32"):
    """Mean next-token cross-entropy of ``x`` (B, T) against ``y`` (B, T)."""
    return _loss(params, x, y, heads, precision)


@_with_precision
@functools.partial(jax.jit, static_argnames=("heads", "precision"))
def loss_and_grad(params, x, y, *, heads: int, precision: str = "float32"):
    """(loss, gradient tree in float32) of :func:`loss`."""
    value, grads = jax.value_and_grad(_loss)(params, x, y, heads, precision)
    return value, jax.tree.map(lambda g: g.astype(jnp.float32), grads)


def mean_loss_and_grad(params, xs, ys, *, heads: int, rows: int,
                       precision: str = "float32"):
    """Loss and gradient averaged over ``xs``/``ys`` (N, T), taken ``rows``
    sequences at a time so that a 124M model's activations fit beside its
    weights.  ``N`` must be a multiple of ``rows``."""
    n = xs.shape[0]
    if n % rows:
        raise ValueError(f"{n} sequences do not split into groups of {rows}")
    total, acc = 0.0, None
    for i in range(0, n, rows):
        value, grads = loss_and_grad(params, xs[i:i + rows], ys[i:i + rows],
                                     heads=heads, precision=precision)
        total += float(value)
        acc = grads if acc is None else jax.tree.map(jnp.add, acc, grads)
    k = n // rows
    return total / k, jax.tree.map(lambda g: g / k, acc)


def greedy_continue(params, prompt, new_tokens: int, *, heads: int,
                    block: int, precision: str):
    """Greedy decoding by full recomputation (no cache): the tokens a model
    of this precision continues ``prompt`` with.  The serving control."""
    seq = list(int(t) for t in prompt)
    buf = np.zeros((1, block), np.int32)
    for _ in range(new_tokens):
        buf[0, :len(seq)] = seq
        z = logits(params, jnp.asarray(buf), heads=heads, precision=precision)
        seq.append(int(jnp.argmax(z[0, len(seq) - 1])))
    return seq[len(prompt):]


def greedy_regret(params, prompt, generated, *, heads: int, block: int,
                  chosen_by: str | None = None):
    """How far each generated token is from the float32 reference's own
    greedy choice, teacher-forced on the sequence as generated: for every
    generated position, (largest reference logit − reference logit of the
    token that was emitted) ÷ the standard deviation of that position's
    logits.  0 where the emitted token is the reference's argmax.  Returns a
    float array, one entry per generated token.

    ``chosen_by`` names a precision: then the token judged at each position
    is not the emitted one but the one that precision puts first on the same
    prefix — the control's reading at the program's own positions, without
    decoding."""
    seq = list(prompt) + list(generated)
    if len(seq) > block:
        raise ValueError(f"{len(seq)} tokens exceed the block of {block}")
    buf = np.zeros((1, block), np.int32)
    buf[0, :len(seq)] = seq
    span = slice(len(prompt) - 1, len(seq) - 1)
    rows = logits(params, jnp.asarray(buf), heads=heads)[0][span]
    tokens = (jnp.asarray(generated, jnp.int32) if chosen_by is None
              else jnp.argmax(logits(params, jnp.asarray(buf), heads=heads,
                                     precision=chosen_by)[0][span], -1))
    chosen = jnp.take_along_axis(rows, tokens[:, None], -1)[:, 0]
    return np.asarray((rows.max(-1) - chosen) / rows.std(-1), np.float64)


def tree_rel_error(got: dict, want: dict) -> float:
    """‖got − want‖ / ‖want‖ over two flat dicts of arrays with equal keys."""
    num = sum(float(jnp.sum((jnp.asarray(got[k], jnp.float32)
                             - jnp.asarray(want[k], jnp.float32)) ** 2))
              for k in want)
    den = sum(float(jnp.sum(jnp.asarray(want[k], jnp.float32) ** 2))
              for k in want)
    return (num / den) ** 0.5


# ---------------------------------------------------------------------------
# the program's side: which preset builds this architecture, and with what
# ---------------------------------------------------------------------------

PRESET = "gpt2_custom"


def preset_args(cfg: dict) -> dict:
    """Arguments of ``penroz_tpu.models.presets.gpt2_custom`` for ``cfg``."""
    return dims(cfg)
