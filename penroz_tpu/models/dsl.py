"""JSON layer/optimizer DSL → functional module trees and optax optimizers.

The TPU-native equivalent of the reference's ``mappers.py``:

- a registry of layer algos (reference: mappers.py:19-41) building the
  functional modules in ``penroz_tpu.ops.modules``;
- weight-init overrides (``normal``/``xavier_uniform``/``kaiming_uniform``/
  ``zeros``) plus ``confidence`` weight scaling (reference: mappers.py:43-51,
  63-99);
- an optimizer registry over optax (reference: mappers.py:53-57, 264-274);
- HuggingFace config → DSL builders for GPT-2 and the Gemma family
  (reference: mappers.py:121-262) and HF state-dict → flat-param-dict key
  remapping (reference: mappers.py:304-448).

Parameter key names mirror the reference's torch ``state_dict`` naming
(``layers.{i}...``) so checkpoints and HF imports stay pure table lookups.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from penroz_tpu.ops import modules as M

# Init-override keys that may sit alongside the layer algo in a DSL entry
# (reference: mappers.py:43-51; ``confidence`` scaling: mappers.py:88-93).
INIT_KEYS = ("normal", "xavier_uniform", "kaiming_uniform", "zeros")

_CONTAINER_ALGOS = {
    "sequential": M.Sequential,
    "summation": M.Summation,
    "residual": M.ResidualConnection,
    "parallelresidual": M.ParallelResidual,
}

_LEAF_ALGOS = {
    "linear": M.Linear,
    "embedding": M.Embedding,
    "position": M.PositionEmbedding,
    "scaledembedding": M.ScaledEmbedding,
    "flatten": M.Flatten,
    "batchnorm1d": M.BatchNorm1d,
    "layernorm": M.LayerNorm,
    "rmsnorm": M.RMSNorm,
    "relu": M.ReLU,
    "gelu": M.GELU,
    "silu": M.SiLU,
    "sigmoid": M.Sigmoid,
    "tanh": M.Tanh,
    "softmax": M.Softmax,
    "softmaxlast": M.SoftmaxOnLast,
    "dropout": M.Dropout,
    "attention": M.CausalSelfAttention,
    "latentattention": M.LatentAttention,
    "ssm": M.GatedSSM,
    "mamba2": M.Mamba2Mixer,
    "gatedmlp": M.GatedMLP,
    "moe": M.MixtureOfExperts,
    "clamp": M.Clamp,
    "softcap": M.Softcap,
}

_OPTIMIZERS = ("adamw", "adam", "sgd")


def layer_algo(entry: dict) -> str:
    """The single layer-algo key of a DSL entry (init keys are siblings)."""
    algos = [k for k in entry if k not in INIT_KEYS and k != "confidence"]
    if len(algos) != 1:
        raise ValueError(f"Layer entry must have exactly one algo key, got "
                         f"{sorted(entry)}")
    return algos[0]


def to_layer(entry: dict) -> M.Module:
    """Recursively build one module from a DSL entry (reference:
    mappers.py:63-99)."""
    algo = layer_algo(entry)
    args = entry[algo]
    if algo in _CONTAINER_ALGOS:
        mod = _CONTAINER_ALGOS[algo](*[to_layer(e) for e in args])
    elif algo == "transformerblock":
        kwargs: dict[str, Any] = {
            "attn_block": to_layer(args["attn_block"]),
            "mlp_block": to_layer(args["mlp_block"]),
            "post_norm_on_residual": bool(args.get("post_norm_on_residual",
                                                   True)),
        }
        for name in ("post_attn_norm", "post_mlp_norm"):
            if name in args:
                kwargs[name] = to_layer(args[name])
        mod = M.TransformerBlock(**kwargs)
    elif algo == "looped":
        # one stack of blocks run ``steps`` times with shared weights, an
        # exit (final norm, head, gate) after every pass: ops/modules.Looped
        unknown = set(args) - {"steps", "body", "exit", "entropy_weight"}
        missing = {"steps", "body", "exit"} - set(args)
        if unknown or missing or set(args["exit"]) != {"norm", "head",
                                                        "gate"}:
            raise ValueError(
                "looped takes steps, body (a list of blocks), exit "
                "({norm, head, gate}) and optionally entropy_weight; got "
                f"{sorted(args)}")
        mod = M.Looped(
            steps=args["steps"], body=[to_layer(e) for e in args["body"]],
            **{k: to_layer(args["exit"][k]) for k in ("norm", "head", "gate")},
            **({"entropy_weight": args["entropy_weight"]}
               if "entropy_weight" in args else {}))
    elif algo == "hyperconnected":
        # one sub-block on a residual path of several mixed streams:
        # ops/modules.HyperConnected
        if "body" not in args or "features" not in args:
            raise ValueError("hyperconnected takes features, body (one "
                             f"layer entry) and its options; got "
                             f"{sorted(args)}")
        mod = M.HyperConnected(
            body=to_layer(args["body"]),
            **{k: v for k, v in args.items() if k != "body"})
    elif algo == "mixerblock":
        # one mixer a layer, x + mixer(norm(x)): ops/modules.MixerBlock
        if set(args) != {"norm", "mixer"}:
            raise ValueError("mixerblock takes norm and mixer (one layer "
                             f"entry each); got {sorted(args)}")
        mod = M.MixerBlock(norm=to_layer(args["norm"]),
                           mixer=to_layer(args["mixer"]))
    elif algo in _LEAF_ALGOS:
        mod = _LEAF_ALGOS[algo](**args)
    else:
        raise ValueError(f"Unsupported layer: {algo}")
    mod._algo = algo
    mod._init_spec = {k: entry[k] for k in entry
                      if k in INIT_KEYS or k == "confidence"}
    return mod


def build_modules(layers: list[dict]) -> list[M.Module]:
    """Build + bind the top-level module list (param prefix ``layers.{i}``)."""
    mods = [to_layer(entry) for entry in layers]
    for i, mod in enumerate(mods):
        mod.bind(f"layers.{i}")
    return mods


def _fans(shape: tuple) -> tuple[int, int]:
    """(fan_in, fan_out) for a weight stored as (out, in) — torch layout."""
    if len(shape) >= 2:
        return int(shape[-1]), int(shape[0])
    return int(shape[0]), int(shape[0])


def _override_init(mod: M.Module, params: dict, spec: dict, rng) -> dict:
    """Apply an init-override spec to a module's own params (reference:
    mappers.py:63-99: per-layer init + confidence weight scaling)."""
    shapes = mod.param_shapes()
    wkey = mod.key("weight")
    if "weight" in shapes and wkey in params:
        shape = shapes["weight"]
        fan_in, fan_out = _fans(shape)
        w = params[wkey]
        if "normal" in spec:
            mean = float(spec["normal"].get("mean", 0.0))
            std = float(spec["normal"].get("std", 1.0))
            w = jax.random.normal(jax.random.fold_in(rng, 101), shape,
                                  jnp.float32) * std + mean
        elif "xavier_uniform" in spec:
            bound = math.sqrt(6.0 / (fan_in + fan_out))
            w = jax.random.uniform(jax.random.fold_in(rng, 102), shape,
                                   jnp.float32, -bound, bound)
        elif "kaiming_uniform" in spec:
            a = float(spec["kaiming_uniform"].get("a", math.sqrt(5.0)))
            nonlinearity = spec["kaiming_uniform"].get("nonlinearity",
                                                       "leaky_relu")
            if nonlinearity == "relu":
                gain = math.sqrt(2.0)
            elif nonlinearity == "leaky_relu":
                gain = math.sqrt(2.0 / (1.0 + a * a))
            else:
                gain = 1.0
            bound = gain * math.sqrt(3.0 / fan_in)
            w = jax.random.uniform(jax.random.fold_in(rng, 103), shape,
                                   jnp.float32, -bound, bound)
        if "confidence" in spec:
            w = w * float(spec["confidence"])
        params[wkey] = w
    bkey = mod.key("bias")
    if "zeros" in spec and bkey in params:
        params[bkey] = jnp.zeros(shapes["bias"], jnp.float32)
    return params


def init_module_params(mods: list[M.Module], seed: int = 0):
    """Deterministically initialize the flat param/buffer dicts for a bound
    module list, honoring per-layer init-override specs."""
    base = jax.random.key(seed)
    params: dict[str, jax.Array] = {}
    buffers: dict[str, jax.Array] = {}
    idx = 0
    for top in mods:
        for sub in top.walk():
            idx += 1
            rng = jax.random.fold_in(base, idx)
            own = sub.init(rng)
            spec = getattr(sub, "_init_spec", None)
            if spec:
                own = _override_init(sub, own, spec, rng)
            params.update(own)
            buffers.update(sub.init_buffers())
    return params, buffers


def build_optimizer(config: dict) -> optax.GradientTransformation:
    """Optimizer DSL → optax transform (reference: mappers.py:53-57,264-274).

    ``betas`` lists are coerced to the (b1, b2) pair; ``weight_decay`` follows
    torch semantics (decoupled for adamw, L2-into-grad for adam/sgd).
    """
    if len(config) != 1:
        raise ValueError(f"Optimizer config must have exactly one key, got "
                         f"{sorted(config)}")
    (name, args), = config.items()
    if name not in _OPTIMIZERS:
        raise ValueError(f"Unsupported optimizer: {name}")
    args = dict(args)
    lr = float(args.pop("lr", 1e-3))
    if name in ("adamw", "adam"):
        betas = args.pop("betas", (0.9, 0.999))
        b1, b2 = float(betas[0]), float(betas[1])
        eps = float(args.pop("eps", 1e-8))
        if name == "adamw":
            weight_decay = float(args.pop("weight_decay", 0.01))
            return optax.adamw(lr, b1=b1, b2=b2, eps=eps,
                               weight_decay=weight_decay)
        weight_decay = float(args.pop("weight_decay", 0.0))
        opt = optax.adam(lr, b1=b1, b2=b2, eps=eps)
        if weight_decay:
            return optax.chain(optax.add_decayed_weights(weight_decay), opt)
        return opt
    momentum = float(args.pop("momentum", 0.0)) or None
    nesterov = bool(args.pop("nesterov", False))
    weight_decay = float(args.pop("weight_decay", 0.0))
    opt = optax.sgd(lr, momentum=momentum, nesterov=nesterov)
    if weight_decay:
        return optax.chain(optax.add_decayed_weights(weight_decay), opt)
    return opt


class Mapper:
    """Layer + optimizer DSL front-end (reference: mappers.py `Mapper`)."""

    def __init__(self, layers: list[dict], optimizer: dict):
        self.layers = layers
        self.optimizer = optimizer

    def to_modules(self) -> list[M.Module]:
        return build_modules(self.layers)

    def init_params(self, mods: list[M.Module], seed: int = 0):
        return init_module_params(mods, seed=seed)

    def to_optimizer(self) -> optax.GradientTransformation:
        return build_optimizer(self.optimizer)

    # -- HuggingFace config → DSL ------------------------------------------

    @staticmethod
    def from_hf_config(config, n_layer_override: Optional[int] = None
                       ) -> list[dict]:
        """Build the layer DSL for a HuggingFace model config (reference:
        mappers.py:121-262 for GPT-2 and Gemma 1/2/3/4)."""
        model_type = getattr(config, "model_type", "") or ""
        if model_type == "gpt2":
            return _gpt2_dsl_from_config(config, n_layer_override)
        if model_type.startswith("gemma"):
            if model_type.startswith("gemma3n"):
                # Gemma-3n checkpoints carry AltUp, LAuReL, and per-layer
                # input projections this builder does not implement —
                # routing them through the generic gemma path would
                # import with silently wrong logits.  (The reference's
                # "gemma 4" dims-only surface — kv-shared layers,
                # double-wide MLPs, per-type head dims — stays available
                # for configs without those mechanisms.)
                raise ValueError(
                    "gemma3n checkpoints are not supported (AltUp/LAuReL "
                    "architecture)")
            return _gemma_dsl_from_config(config, n_layer_override)
        if model_type in _LLAMA_FAMILY:
            return _llama_dsl_from_config(config, n_layer_override)
        if model_type == "gpt_neox":
            return _neox_dsl_from_config(config, n_layer_override)
        if model_type == "phi":
            return _phi_dsl_from_config(config, n_layer_override)
        if model_type == "olmo2":
            return _olmo2_dsl_from_config(config, n_layer_override)
        if model_type == "olmo":
            return _olmo_dsl_from_config(config, n_layer_override)
        if model_type == "stablelm":
            return _stablelm_dsl_from_config(config, n_layer_override)
        if model_type == "gptj":
            return _gptj_dsl_from_config(config, n_layer_override)
        if model_type == "falcon":
            return _falcon_dsl_from_config(config, n_layer_override)
        if model_type == "gpt_bigcode":
            return _bigcode_dsl_from_config(config, n_layer_override)
        if model_type == "opt":
            return _opt_dsl_from_config(config, n_layer_override)
        if model_type == "bloom":
            return _bloom_dsl_from_config(config, n_layer_override)
        if model_type == "mpt":
            return _mpt_dsl_from_config(config, n_layer_override)
        if model_type == "ouro":
            return _ouro_dsl_from_config(config, n_layer_override)
        raise ValueError(f"Unsupported HuggingFace model type: {model_type}")

    # -- HF state-dict detection + remapping --------------------------------

    @staticmethod
    def detect_hf_n_layer(state_dict: dict) -> int:
        """Sniff the transformer layer count from state-dict key names
        (reference: mappers.py:276-302)."""
        import re
        pattern = re.compile(
            r"(?:transformer\.h|transformer\.blocks|gpt_neox\.layers"
            r"|model\.decoder\.layers"
            r"|model\.(?:language_model\.)?layers)\.(\d+)\.")
        n = 0
        for key in state_dict:
            m = pattern.match(key)
            if m:
                n = max(n, int(m.group(1)) + 1)
        return n

    @staticmethod
    def map_hf_state_dict_to_custom(state_dict: dict, n_layer: int,
                                    config=None) -> dict:
        """Remap an HF state dict (numpy arrays) onto our flat param keys
        (reference: mappers.py:304-448)."""
        if getattr(config, "model_type", "") == "gptj" or \
                "transformer.h.0.attn.q_proj.weight" in state_dict:
            return _map_gptj_state_dict(state_dict, n_layer, config)
        if getattr(config, "model_type", "") == "gpt_bigcode":
            # checked BEFORE the gpt2 key sniff: bigcode checkpoints also
            # carry transformer.wte.weight but use plain nn.Linear layouts
            return _map_bigcode_state_dict(state_dict, n_layer, config)
        if getattr(config, "model_type", "") == "mpt" or \
                "transformer.blocks.0.attn.Wqkv.weight" in state_dict:
            # also before the gpt2 sniff: MPT carries transformer.wte too
            return _map_mpt_state_dict(state_dict, n_layer, config)
        if "transformer.wte.weight" in state_dict:
            # Config-less safety sniff: GPT-2 Conv1D stores c_attn as
            # (d, 3d); gpt_bigcode/falcon-style nn.Linear layouts are
            # (out, in) and would be silently transposed into garbage by
            # the GPT-2 branch.  Refuse loudly instead of mis-mapping.
            w = state_dict.get("transformer.h.0.attn.c_attn.weight")
            if config is None and w is not None \
                    and w.shape[1] != 3 * w.shape[0]:
                raise ValueError(
                    "state dict has transformer.wte.weight but c_attn is "
                    f"not Conv1D-shaped ({tuple(w.shape)}); pass the HF "
                    "config so the family (gpt_bigcode/falcon/...) can be "
                    "dispatched correctly")
            return _map_gpt2_state_dict(state_dict, n_layer)
        if "gpt_neox.embed_in.weight" in state_dict:
            return _map_neox_state_dict(state_dict, n_layer, config)
        if getattr(config, "model_type", "") == "opt" or \
                "model.decoder.embed_tokens.weight" in state_dict:
            return _map_opt_state_dict(state_dict, n_layer, config)
        if getattr(config, "model_type", "") == "bloom" or \
                "transformer.word_embeddings_layernorm.weight" in state_dict:
            # the embedding LayerNorm is BLOOM-unique; plain
            # word_embeddings would also match Falcon checkpoints
            return _map_bloom_state_dict(state_dict, n_layer, config)
        if getattr(config, "model_type", "") == "phi":
            return _map_phi_state_dict(state_dict, n_layer, config)
        if getattr(config, "model_type", "") == "olmo2":
            return _map_olmo2_state_dict(state_dict, n_layer, config)
        if getattr(config, "model_type", "") == "olmo":
            return _map_olmo_state_dict(state_dict, n_layer, config)
        if getattr(config, "model_type", "") == "stablelm":
            return _map_stablelm_state_dict(state_dict, n_layer, config)
        if getattr(config, "model_type", "") == "falcon":
            return _map_falcon_state_dict(state_dict, n_layer, config)
        if getattr(config, "model_type", "") in _LLAMA_FAMILY:
            return _map_llama_state_dict(state_dict, n_layer, config)
        return _map_gemma_state_dict(state_dict, n_layer, config)


# ---------------------------------------------------------------------------
# GPT-2
# ---------------------------------------------------------------------------

def _gpt2_gelu_entry(activation: str) -> dict:
    if activation in ("gelu_new", "gelu_pytorch_tanh"):
        return {"gelu": {"approximate": "tanh"}}
    return {"gelu": {}}


def _gpt2_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """GPT-2 HF config → layer DSL (reference: mappers.py:121-176)."""
    d = int(config.n_embd)
    n = int(n_layer_override if n_layer_override else config.n_layer)
    heads = int(config.n_head)
    vocab = int(config.vocab_size)
    block = int(config.n_positions)
    attn_drop = float(getattr(config, "attn_pdrop", 0.0) or 0.0)
    resid_drop = float(getattr(config, "resid_pdrop", 0.0) or 0.0)
    embd_drop = float(getattr(config, "embd_pdrop", 0.0) or 0.0)
    gelu = _gpt2_gelu_entry(getattr(config, "activation_function", "gelu_new"))
    proj_std = 0.02 / math.sqrt(2 * n)

    layers: list[dict] = [
        {"summation": [
            {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
             "normal": {"mean": 0.0, "std": 0.02}},
            {"position": {"num_embeddings": block, "embedding_dim": d},
             "normal": {"mean": 0.0, "std": 0.02}}]},
        {"dropout": {"p": embd_drop}},
    ]
    for _ in range(n):
        layers.append({"residual": [
            {"sequential": [
                {"layernorm": {"normalized_shape": d}},
                {"linear": {"in_features": d, "out_features": 3 * d},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                {"attention": {"num_heads": heads, "dropout": attn_drop}},
                {"linear": {"in_features": d, "out_features": d},
                 "normal": {"mean": 0.0, "std": proj_std}, "zeros": {}},
                {"dropout": {"p": resid_drop}}]},
            {"sequential": [
                {"layernorm": {"normalized_shape": d}},
                {"linear": {"in_features": d, "out_features": 4 * d},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                gelu,
                {"linear": {"in_features": 4 * d, "out_features": d},
                 "normal": {"mean": 0.0, "std": proj_std}, "zeros": {}},
                {"dropout": {"p": resid_drop}}]}]})
    layers += [
        {"layernorm": {"normalized_shape": d}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": False},
         "normal": {"mean": 0.0, "std": 0.02}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _bloom_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """BLOOM HF config → layer DSL: NO positional embedding at all —
    ALiBi linear logit biases carry position (attention ``alibi`` arg) —
    plus the embedding LayerNorm, pre-LN blocks with per-head-interleaved
    fused QKV (de-interleaved at import), and tanh-GELU MLPs."""
    d = int(config.hidden_size)
    n = int(n_layer_override if n_layer_override else config.n_layer)
    heads = int(config.n_head)
    vocab = int(config.vocab_size)
    if getattr(config, "apply_residual_connection_post_layernorm", False):
        # HF adds the post-LN output (not the block input) to the
        # residual for these checkpoints — structurally different blocks;
        # refuse instead of importing wrong logits.
        raise ValueError("BLOOM apply_residual_connection_post_layernorm="
                         "True is not supported")
    drop = float(getattr(config, "hidden_dropout", 0.0) or 0.0)
    attn_drop = float(getattr(config, "attention_dropout", 0.0) or 0.0)

    layers: list[dict] = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
         "normal": {"mean": 0.0, "std": 0.02}},
        {"layernorm": {"normalized_shape": d}},  # word_embeddings_layernorm
    ]
    for _ in range(n):
        layers.append({"residual": [
            {"sequential": [
                {"layernorm": {"normalized_shape": d}},
                {"linear": {"in_features": d, "out_features": 3 * d},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                {"attention": {"num_heads": heads, "dropout": attn_drop,
                               "alibi": True}},
                {"linear": {"in_features": d, "out_features": d},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                {"dropout": {"p": drop}}]},
            {"sequential": [
                {"layernorm": {"normalized_shape": d}},
                {"linear": {"in_features": d, "out_features": 4 * d},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                {"gelu": {"approximate": "tanh"}},  # BloomGelu
                {"linear": {"in_features": 4 * d, "out_features": d},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                {"dropout": {"p": drop}}]}]})
    layers += [
        {"layernorm": {"normalized_shape": d}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": False},
         "normal": {"mean": 0.0, "std": 0.02}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _map_bloom_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """BLOOM HF keys → ours.  The fused ``query_key_value`` is PER-HEAD
    interleaved — rows grouped ``[h0: q,k,v | h1: q,k,v | …]`` as
    ``(H, 3, D, d)`` — while our attention expects ``[all q | all k |
    all v]``; the transpose happens here, at import, so no runtime
    layout variant exists."""
    pfx = "transformer"
    cfg = _llama_text_config(config)
    if cfg is None or getattr(cfg, "n_head", None) is None:
        # Mirror the GPT-2 Conv1D-sniff refusal: the key sniff
        # (word_embeddings_layernorm) dispatches here even config-less, but
        # the per-head QKV de-interleave needs n_head — dying later with a
        # bare AttributeError would hide what is actually missing.
        raise ValueError(
            "BLOOM import requires the HF config (n_head drives the "
            "per-head query_key_value de-interleave); pass the "
            "checkpoint's config to map_hf_state_dict_to_custom")
    heads = int(cfg.n_head)

    def deinterleave(arr):
        return _deinterleave_per_head(arr, heads)

    out = {
        "layers.0.weight": sd[f"{pfx}.word_embeddings.weight"],
        "layers.1.weight": sd[f"{pfx}.word_embeddings_layernorm.weight"],
        "layers.1.bias": sd[f"{pfx}.word_embeddings_layernorm.bias"],
    }
    for i in range(n_layer):
        src = f"{pfx}.h.{i}"
        dst = f"layers.{2 + i}"
        out[f"{dst}.0.0.weight"] = sd[f"{src}.input_layernorm.weight"]
        out[f"{dst}.0.0.bias"] = sd[f"{src}.input_layernorm.bias"]
        qkv = f"{src}.self_attention.query_key_value"
        out[f"{dst}.0.1.weight"] = deinterleave(sd[f"{qkv}.weight"])
        out[f"{dst}.0.1.bias"] = deinterleave(sd[f"{qkv}.bias"])
        out[f"{dst}.0.3.weight"] = sd[f"{src}.self_attention.dense.weight"]
        out[f"{dst}.0.3.bias"] = sd[f"{src}.self_attention.dense.bias"]
        out[f"{dst}.1.0.weight"] = \
            sd[f"{src}.post_attention_layernorm.weight"]
        out[f"{dst}.1.0.bias"] = sd[f"{src}.post_attention_layernorm.bias"]
        out[f"{dst}.1.1.weight"] = sd[f"{src}.mlp.dense_h_to_4h.weight"]
        out[f"{dst}.1.1.bias"] = sd[f"{src}.mlp.dense_h_to_4h.bias"]
        out[f"{dst}.1.3.weight"] = sd[f"{src}.mlp.dense_4h_to_h.weight"]
        out[f"{dst}.1.3.bias"] = sd[f"{src}.mlp.dense_4h_to_h.bias"]
    out[f"layers.{2 + n_layer}.weight"] = sd[f"{pfx}.ln_f.weight"]
    out[f"layers.{2 + n_layer}.bias"] = sd[f"{pfx}.ln_f.bias"]
    out[f"layers.{3 + n_layer}.weight"] = sd.get(
        "lm_head.weight", sd[f"{pfx}.word_embeddings.weight"])
    return out


def _mpt_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """MPT HF config → layer DSL: ALiBi attention (no positional
    embedding), weight-only LayerNorms, bias-free projections, fused
    ``Wqkv`` already in our [q|k|v] layout, exact-GELU 4× MLPs, optional
    ``clip_qkv`` clamp (the OLMo v1 mechanism).

    Refused loudly (wrong math otherwise): ``alibi=False`` checkpoints
    (learned-position MPTs), non-``multihead_attention`` attn types,
    ``qk_ln``, custom ``softmax_scale``, and non-power-of-two head
    counts — MPT's non-pow2 slope interleave differs from the standard
    ALiBi formula our attention computes."""
    import math as _math
    d = int(config.d_model)
    n = int(n_layer_override if n_layer_override else config.n_layers)
    heads = int(config.n_heads)
    vocab = int(config.vocab_size)
    eps = float(getattr(config, "layer_norm_epsilon", 1e-5))
    no_bias = bool(getattr(config, "no_bias", True))
    expansion = int(getattr(config, "expansion_ratio", 4))
    attn_cfg = getattr(config, "attn_config", None)
    get = (attn_cfg.get if isinstance(attn_cfg, dict)
           else lambda k, dflt=None: getattr(attn_cfg, k, dflt))
    if attn_cfg is None or not get("alibi", False):
        raise ValueError("MPT without alibi (learned-position variants) "
                         "is not supported")
    if get("attn_type", "multihead_attention") != "multihead_attention":
        raise ValueError(f"MPT attn_type {get('attn_type')!r} is not "
                         "supported (multihead_attention only)")
    if get("qk_ln", False):
        raise ValueError("MPT qk_ln is not supported")
    if get("softmax_scale") is not None:
        raise ValueError("MPT custom softmax_scale is not supported")
    if not _math.log2(heads).is_integer():
        raise ValueError(
            f"MPT with non-power-of-two heads ({heads}) is not supported: "
            "its slope interleave differs from the standard ALiBi formula")
    clip = get("clip_qkv")
    attn_drop = float(get("attn_pdrop", 0.0) or 0.0)

    layers: list[dict] = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
         "normal": {"mean": 0.0, "std": 0.02}},
    ]
    for _ in range(n):
        attn_items = [
            {"layernorm": {"normalized_shape": d, "eps": eps,
                           "bias": False}},
            {"linear": {"in_features": d, "out_features": 3 * d,
                        "bias": not no_bias},
             "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
        ]
        if clip is not None:
            attn_items.append({"clamp": {"min": -float(clip),
                                         "max": float(clip)}})
        attn_items += [
            # head_dim explicit: the optional clamp between the QKV
            # linear and the attention breaks adjacency-based inference
            {"attention": {"num_heads": heads, "dropout": attn_drop,
                           "alibi": True, "head_dim": d // heads}},
            {"linear": {"in_features": d, "out_features": d,
                        "bias": not no_bias},
             "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
        ]
        layers.append({"residual": [
            {"sequential": attn_items},
            {"sequential": [
                {"layernorm": {"normalized_shape": d, "eps": eps,
                               "bias": False}},
                {"linear": {"in_features": d,
                            "out_features": expansion * d,
                            "bias": not no_bias},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                {"gelu": {}},  # MptMLP: nn.GELU(approximate="none")
                {"linear": {"in_features": expansion * d,
                            "out_features": d, "bias": not no_bias},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}}]}]})
    layers += [
        {"layernorm": {"normalized_shape": d, "eps": eps, "bias": False}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": False},
         "normal": {"mean": 0.0, "std": 0.02}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _map_mpt_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """MPT HF keys → ours: straight copies — ``Wqkv`` is already fused in
    our [q|k|v] row order, the LayerNorms carry weights only, and the
    clamp entry (clip_qkv) shifts the attention branch's item indices
    exactly like OLMo v1."""
    cfg = _llama_text_config(config)
    attn_cfg = getattr(cfg, "attn_config", None) if cfg is not None else None
    get = (attn_cfg.get if isinstance(attn_cfg, dict)
           else lambda k, dflt=None: getattr(attn_cfg, k, dflt))
    has_clip = attn_cfg is not None and get("clip_qkv") is not None
    i_out = 4 if has_clip else 3  # [ln, qkv, (clamp,) attention, out]
    # Refuse-loudly contract: every HF MptConfig ships weight-only norms
    # (verified against transformers — even no_bias=False leaves them
    # bias-free), and the DSL hardcodes bias:False accordingly.  A future
    # variant shipping norm biases must fail here, not import silently
    # without them.
    norm_bias_keys = sorted(
        k for k in sd
        if k.endswith((".norm_1.bias", ".norm_2.bias"))
        or k == "transformer.norm_f.bias")
    if norm_bias_keys:
        raise ValueError(
            "MPT checkpoint carries LayerNorm biases "
            f"({norm_bias_keys[:3]}...); this importer maps MPT norms as "
            "weight-only (every released MptConfig) and refuses rather "
            "than dropping the biases")
    out = {"layers.0.weight": sd["transformer.wte.weight"]}
    for i in range(n_layer):
        src = f"transformer.blocks.{i}"
        dst = f"layers.{1 + i}"
        out[f"{dst}.0.0.weight"] = sd[f"{src}.norm_1.weight"]
        out[f"{dst}.0.1.weight"] = sd[f"{src}.attn.Wqkv.weight"]
        if f"{src}.attn.Wqkv.bias" in sd:
            out[f"{dst}.0.1.bias"] = sd[f"{src}.attn.Wqkv.bias"]
        out[f"{dst}.0.{i_out}.weight"] = sd[f"{src}.attn.out_proj.weight"]
        if f"{src}.attn.out_proj.bias" in sd:
            out[f"{dst}.0.{i_out}.bias"] = sd[f"{src}.attn.out_proj.bias"]
        out[f"{dst}.1.0.weight"] = sd[f"{src}.norm_2.weight"]
        out[f"{dst}.1.1.weight"] = sd[f"{src}.ffn.up_proj.weight"]
        out[f"{dst}.1.3.weight"] = sd[f"{src}.ffn.down_proj.weight"]
        if f"{src}.ffn.up_proj.bias" in sd:
            out[f"{dst}.1.1.bias"] = sd[f"{src}.ffn.up_proj.bias"]
            out[f"{dst}.1.3.bias"] = sd[f"{src}.ffn.down_proj.bias"]
    out[f"layers.{1 + n_layer}.weight"] = sd["transformer.norm_f.weight"]
    out[f"layers.{2 + n_layer}.weight"] = sd.get(
        "lm_head.weight", sd["transformer.wte.weight"])
    return out


def _opt_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """OPT HF config → layer DSL: GPT-2-shaped pre-LN blocks with
    separate-then-fused biased QKV, ReLU MLPs, and LEARNED positions
    whose +2 row offset (HF OPTLearnedPositionalEmbedding) is folded
    away at import time by dropping the table's first two rows — no
    runtime position hack survives.

    Refused loudly: ``do_layer_norm_before=False`` (OPT-350m post-norm
    ordering) and ``word_embed_proj_dim != hidden_size`` (the 350m
    in/out projections) — silently approximating either would import
    wrong logits.
    """
    d = int(config.hidden_size)
    n = int(n_layer_override if n_layer_override else
            config.num_hidden_layers)
    if not getattr(config, "do_layer_norm_before", True):
        raise ValueError("OPT do_layer_norm_before=False (350m post-norm "
                         "ordering) is not supported")
    proj_dim = getattr(config, "word_embed_proj_dim", d) or d
    if int(proj_dim) != d:
        raise ValueError("OPT word_embed_proj_dim != hidden_size "
                         "(embedding in/out projections) is not supported")
    heads = int(config.num_attention_heads)
    vocab = int(config.vocab_size)
    block = int(config.max_position_embeddings)
    ffn = int(getattr(config, "ffn_dim", 4 * d))
    bias = bool(getattr(config, "enable_bias", True))
    act = str(getattr(config, "activation_function", "relu"))
    act_entry = _gelu_entry(act, "opt")  # raises on unsupported strings
    # HF OPT applies `dropout` to the embedding and BOTH residual streams
    # and `attention_dropout` to the attention probabilities — distinct
    # knobs (opt-125m ships 0.1 / 0.0).
    drop = float(getattr(config, "dropout", 0.0) or 0.0)
    attn_drop = float(getattr(config, "attention_dropout", 0.0) or 0.0)

    layers: list[dict] = [
        {"summation": [
            {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
             "normal": {"mean": 0.0, "std": 0.02}},
            {"position": {"num_embeddings": block, "embedding_dim": d},
             "normal": {"mean": 0.0, "std": 0.02}}]},
        {"dropout": {"p": drop}},
    ]
    for _ in range(n):
        layers.append({"residual": [
            {"sequential": [
                {"layernorm": {"normalized_shape": d}},
                {"linear": {"in_features": d, "out_features": 3 * d,
                            "bias": bias},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                {"attention": {"num_heads": heads, "dropout": attn_drop}},
                {"linear": {"in_features": d, "out_features": d,
                            "bias": bias},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                {"dropout": {"p": drop}}]},
            {"sequential": [
                {"layernorm": {"normalized_shape": d}},
                {"linear": {"in_features": d, "out_features": ffn,
                            "bias": bias},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                act_entry,
                {"linear": {"in_features": ffn, "out_features": d,
                            "bias": bias},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                {"dropout": {"p": drop}}]}]})
    layers += [
        {"layernorm": {"normalized_shape": d}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": False},
         "normal": {"mean": 0.0, "std": 0.02}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _map_opt_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """OPT HF keys → ours.  ``model.decoder.*`` layout, separate q/k/v
    fused by concatenation, and the learned position table's first two
    rows DROPPED (HF looks positions up at ``pos + 2``; with full
    attention masks that is exactly a 0-based lookup into ``table[2:]``,
    including cached decode where our offset is the cache length)."""
    dec = "model.decoder"
    out = {
        "layers.0.0.weight": sd[f"{dec}.embed_tokens.weight"],
        "layers.0.1.weight":
            np.asarray(sd[f"{dec}.embed_positions.weight"])[2:],
    }
    for i in range(n_layer):
        src = f"{dec}.layers.{i}"
        dst = f"layers.{2 + i}"
        _concat_qkv(sd, src, out, f"{dst}.0.1")
        out[f"{dst}.0.0.weight"] = sd[f"{src}.self_attn_layer_norm.weight"]
        out[f"{dst}.0.0.bias"] = sd[f"{src}.self_attn_layer_norm.bias"]
        out[f"{dst}.0.3.weight"] = sd[f"{src}.self_attn.out_proj.weight"]
        if f"{src}.self_attn.out_proj.bias" in sd:
            out[f"{dst}.0.3.bias"] = sd[f"{src}.self_attn.out_proj.bias"]
        out[f"{dst}.1.0.weight"] = sd[f"{src}.final_layer_norm.weight"]
        out[f"{dst}.1.0.bias"] = sd[f"{src}.final_layer_norm.bias"]
        out[f"{dst}.1.1.weight"] = sd[f"{src}.fc1.weight"]
        out[f"{dst}.1.3.weight"] = sd[f"{src}.fc2.weight"]
        if f"{src}.fc1.bias" in sd:
            out[f"{dst}.1.1.bias"] = sd[f"{src}.fc1.bias"]
            out[f"{dst}.1.3.bias"] = sd[f"{src}.fc2.bias"]
    out[f"layers.{2 + n_layer}.weight"] = \
        sd[f"{dec}.final_layer_norm.weight"]
    out[f"layers.{2 + n_layer}.bias"] = sd[f"{dec}.final_layer_norm.bias"]
    out[f"layers.{3 + n_layer}.weight"] = sd.get(
        "lm_head.weight", sd[f"{dec}.embed_tokens.weight"])
    return out


def _bigcode_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """GPT-BigCode (StarCoder/SantaCoder) HF config → layer DSL: the
    GPT-2 structure (learned positions, pre-LN sequential residual,
    biased projections) with MULTI-QUERY attention — the fused ``c_attn``
    is ``[all q, k, v]`` with one kv head, exactly our layout — and
    ``nn.Linear`` weights (no Conv1D transpose, unlike GPT-2).
    ``multi_query=False`` checkpoints keep all heads."""
    cfg = _llama_text_config(config)
    if not getattr(cfg, "scale_attn_weights", True):
        raise ValueError("scale_attn_weights=False gpt_bigcode "
                         "checkpoints are not supported; importing would "
                         "produce wrong logits")
    d = int(cfg.n_embd if hasattr(cfg, "n_embd") else cfg.hidden_size)
    n = int(n_layer_override if n_layer_override
            else getattr(cfg, "num_hidden_layers", None) or cfg.n_layer)
    heads = int(getattr(cfg, "num_attention_heads", None) or cfg.n_head)
    kv = 1 if bool(getattr(cfg, "multi_query", True)) else heads
    hd = d // heads
    vocab = int(cfg.vocab_size)
    block = int(getattr(cfg, "n_positions", None)
                or getattr(cfg, "max_position_embeddings", 1024))
    eps = float(getattr(cfg, "layer_norm_epsilon", 1e-5))
    attn_drop = float(getattr(cfg, "attn_pdrop", 0.0) or 0.0)
    resid_drop = float(getattr(cfg, "resid_pdrop", 0.0) or 0.0)
    embd_drop = float(getattr(cfg, "embd_pdrop", 0.0) or 0.0)
    inter = int(getattr(cfg, "n_inner", None) or 4 * d)
    gelu = _gelu_entry(getattr(cfg, "activation_function",
                               "gelu_pytorch_tanh"), "gpt_bigcode")

    layers: list[dict] = [
        {"summation": [
            {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
             "normal": {"mean": 0.0, "std": 0.02}},
            {"position": {"num_embeddings": block, "embedding_dim": d},
             "normal": {"mean": 0.0, "std": 0.02}}]},
        {"dropout": {"p": embd_drop}},
    ]
    for _ in range(n):
        layers.append({"residual": [
            {"sequential": [
                {"layernorm": {"normalized_shape": d, "eps": eps}},
                {"linear": {"in_features": d,
                            "out_features": (heads + 2 * kv) * hd}},
                {"attention": {"num_heads": heads, "num_kv_heads": kv,
                               "dropout": attn_drop}},
                {"linear": {"in_features": heads * hd, "out_features": d}},
                {"dropout": {"p": resid_drop}}]},
            {"sequential": [
                {"layernorm": {"normalized_shape": d, "eps": eps}},
                {"linear": {"in_features": d, "out_features": inter}},
                gelu,
                {"linear": {"in_features": inter, "out_features": d}},
                {"dropout": {"p": resid_drop}}]}]})
    layers += [
        {"layernorm": {"normalized_shape": d, "eps": eps}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": False}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _map_bigcode_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """GPT-BigCode HF keys → ours: plain nn.Linear copies (no Conv1D
    transpose), tied head fallback.  The fused ``c_attn`` is [all q, k,
    v] under multi_query (our layout), but multi_query=False checkpoints
    store it PER-HEAD interleaved [q_h; k_h; v_h] (HF views it as
    (num_heads, 3·head_dim)) — the NeoX de-interleave reorders it."""
    cfg = _llama_text_config(config)
    multi_query = bool(getattr(cfg, "multi_query", True))
    heads = int(getattr(cfg, "num_attention_heads", None) or cfg.n_head)

    def fix_qkv(w):
        return w if multi_query else _neox_deinterleave_qkv(w, heads)

    out = {"layers.0.0.weight": sd["transformer.wte.weight"],
           "layers.0.1.weight": sd["transformer.wpe.weight"]}
    for i in range(n_layer):
        src = f"transformer.h.{i}"
        dst = f"layers.{2 + i}"
        for at, hf, fix in (
                (f"{dst}.0.0", "ln_1", None),
                (f"{dst}.0.1", "attn.c_attn", fix_qkv),
                (f"{dst}.0.3", "attn.c_proj", None),
                (f"{dst}.1.0", "ln_2", None),
                (f"{dst}.1.1", "mlp.c_fc", None),
                (f"{dst}.1.3", "mlp.c_proj", None)):
            w = sd[f"{src}.{hf}.weight"]
            out[f"{at}.weight"] = fix(w) if fix else w
            if f"{src}.{hf}.bias" in sd:
                b = sd[f"{src}.{hf}.bias"]
                out[f"{at}.bias"] = fix(b) if fix else b
    for name in ("weight", "bias"):
        out[f"layers.{2 + n_layer}.{name}"] = sd[f"transformer.ln_f.{name}"]
    out[f"layers.{3 + n_layer}.weight"] = sd.get(
        "lm_head.weight", sd["transformer.wte.weight"])
    return out


def _map_gpt2_state_dict(sd: dict, n_layer: int) -> dict:
    """GPT-2 HF keys → ours; Conv1D weights transposed, lm_head tied to wte
    (reference: mappers.py:333-352)."""
    out = {
        "layers.0.0.weight": sd["transformer.wte.weight"],
        "layers.0.1.weight": sd["transformer.wpe.weight"],
    }
    ln_map = {"ln_1": "0.0", "ln_2": "1.0"}
    conv1d_map = {"attn.c_attn": "0.1", "attn.c_proj": "0.3",
                  "mlp.c_fc": "1.1", "mlp.c_proj": "1.3"}
    for i in range(n_layer):
        src = f"transformer.h.{i}"
        dst = f"layers.{2 + i}"
        for hf_name, ours in ln_map.items():
            out[f"{dst}.{ours}.weight"] = sd[f"{src}.{hf_name}.weight"]
            out[f"{dst}.{ours}.bias"] = sd[f"{src}.{hf_name}.bias"]
        for hf_name, ours in conv1d_map.items():
            # HF Conv1D stores (in, out); our Linear stores (out, in).
            out[f"{dst}.{ours}.weight"] = \
                np.ascontiguousarray(sd[f"{src}.{hf_name}.weight"].T)
            out[f"{dst}.{ours}.bias"] = sd[f"{src}.{hf_name}.bias"]
    out[f"layers.{2 + n_layer}.weight"] = sd["transformer.ln_f.weight"]
    out[f"layers.{2 + n_layer}.bias"] = sd["transformer.ln_f.bias"]
    out[f"layers.{3 + n_layer}.weight"] = sd.get(
        "lm_head.weight", sd["transformer.wte.weight"])
    return out


# ---------------------------------------------------------------------------
# Gemma family
# ---------------------------------------------------------------------------

def _gemma_text_config(config):
    return getattr(config, "text_config", None) or config


def _gemma_rope_theta(cfg, layer_type: str) -> float:
    """Per-layer RoPE theta: Gemma-3's ``rope_local_base_freq`` for
    sliding layers, else prefer a matching per-layer-type
    ``rope_scaling`` entry, fall back to any entry, then to
    ``rope_theta`` (reference: mappers.py:198-222)."""
    if layer_type == "sliding_attention":
        local = getattr(cfg, "rope_local_base_freq", None)
        if local:
            return float(local)
    scaling = getattr(cfg, "rope_scaling", None)
    if isinstance(scaling, dict) and scaling:
        entry = scaling.get(layer_type)
        if not isinstance(entry, dict):
            entry = next(iter(scaling.values()))
        if isinstance(entry, dict) and "rope_theta" in entry:
            return float(entry["rope_theta"])
    theta = getattr(cfg, "rope_theta", None)
    return float(theta) if theta else 10000.0


def _gemma_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """Gemma 1/2/3/4 HF config → layer DSL, incl. GQA dims, per-layer
    heterogeneous ``layer_types`` and double-wide MLPs on KV-shared layers
    (reference: mappers.py:178-262)."""
    model_type = getattr(config, "model_type", "gemma")
    cfg = _gemma_text_config(config)
    d = int(cfg.hidden_size)
    n = int(n_layer_override if n_layer_override else cfg.num_hidden_layers)
    heads = int(cfg.num_attention_heads)
    vocab = int(cfg.vocab_size)
    eps = float(getattr(cfg, "rms_norm_eps", 1e-6))
    attn_drop = float(getattr(cfg, "attention_dropout", 0.0) or 0.0)
    activation = getattr(cfg, "hidden_activation", "gelu_pytorch_tanh")
    layer_types = list(getattr(cfg, "layer_types", None)
                       or ["full_attention"] * n)
    num_kv_shared = int(getattr(cfg, "num_kv_shared_layers", 0) or 0)
    double_wide = bool(getattr(cfg, "use_double_wide_mlp", False))
    # gemma (v1): no post-attn/post-mlp norms; gemma2: norms applied to the
    # branch output; gemma3+: norms applied to the residual sum
    # (reference: neural_net_layers.py:188-225 block variants).
    has_post_norms = model_type != "gemma"
    # HF Gemma3DecoderLayer norms the BRANCH OUTPUT before the residual
    # add, exactly like Gemma-2 (verified against modeling_gemma3); the
    # residual-sum placement is the later-variant convention the
    # reference's block switch models (neural_net_layers.py:188-225).
    post_norm_on_residual = model_type not in ("gemma", "gemma2",
                                               "gemma3", "gemma3_text")
    # Gemma-3 attention ALWAYS per-head-RMS-normalizes q and k (HF
    # Gemma3Attention q_norm/k_norm — zero-centered weights, +1 at
    # import) and its GLOBAL layers may carry linear rope scaling
    # ({'rope_type': 'linear', 'factor': 8.0} on the released >1B
    # configs); local layers rotate with rope_local_base_freq unscaled.
    gemma3 = model_type in ("gemma3", "gemma3_text")
    g3_scaling = None
    if gemma3:
        raw = getattr(cfg, "rope_scaling", None)
        if isinstance(raw, dict) and raw and (
                raw.get("rope_type") or raw.get("type")):
            g3_scaling = {"rope_type": (raw.get("rope_type")
                                        or raw.get("type")),
                          "factor": float(raw.get("factor", 1.0))}

    def head_dim_for(layer_type: str) -> int:
        if layer_type == "full_attention" and \
                getattr(cfg, "global_head_dim", None):
            return int(cfg.global_head_dim)
        return int(cfg.head_dim)

    def kv_heads_for(layer_type: str) -> int:
        if layer_type == "full_attention" and \
                getattr(cfg, "num_global_key_value_heads", None):
            return int(cfg.num_global_key_value_heads)
        return int(cfg.num_key_value_heads)

    layers: list[dict] = [
        {"scaledembedding": {"num_embeddings": vocab, "embedding_dim": d,
                             "scale": d ** 0.5},
         "normal": {"mean": 0.0, "std": 0.02}},
    ]
    for i in range(n):
        layer_type = layer_types[i] if i < len(layer_types) else "full_attention"
        hd = head_dim_for(layer_type)
        kv = kv_heads_for(layer_type)
        inter = int(cfg.intermediate_size)
        if double_wide and num_kv_shared and i >= n - num_kv_shared:
            inter *= 2
        block: dict[str, Any] = {
            "attn_block": {"sequential": [
                {"rmsnorm": {"normalized_shape": d, "eps": eps}},
                {"linear": {"in_features": d,
                            "out_features": (heads + 2 * kv) * hd,
                            "bias": False}},
                {"attention": dict(
                    {"num_heads": heads, "num_kv_heads": kv,
                     "rope_theta": _gemma_rope_theta(cfg, layer_type),
                     "head_dim": hd, "dropout": attn_drop},
                    # Gemma-2: score soft-capping + the
                    # query_pre_attn_scalar scale override (silently
                    # dropping either imports wrong logits on real
                    # checkpoints; tiny-model parity can't catch the cap
                    # because random logits sit far below it)
                    **({"logit_softcap": float(cfg.attn_logit_softcapping)}
                       if getattr(cfg, "attn_logit_softcapping", None)
                       else {}),
                    # omitted when it equals the default head_dim
                    # scaling (Gemma-2 9B, Gemma-3 released configs) so
                    # downstream non-default-scale handling stays off
                    **({"attn_scale":
                        float(cfg.query_pre_attn_scalar) ** -0.5}
                       if (getattr(cfg, "query_pre_attn_scalar", None)
                           and float(cfg.query_pre_attn_scalar) != hd)
                       else {}),
                    **({"qk_norm": True, "qk_norm_eps":
                        eps, "qk_norm_fp32_weight": True}
                       if gemma3 else {}),
                    **({"rope_scaling": g3_scaling}
                       if g3_scaling and layer_type == "full_attention"
                       else {}),
                    # sliding layers get REAL windowed attention (the
                    # reference keeps all attention full causal and maps
                    # layer_types to dims only, mappers.py:224-228)
                    **({"sliding_window": int(cfg.sliding_window)}
                       if layer_type == "sliding_attention"
                       and getattr(cfg, "sliding_window", None) else {}))},
                {"linear": {"in_features": heads * hd, "out_features": d,
                            "bias": False}}]},
            "mlp_block": {"sequential": [
                {"rmsnorm": {"normalized_shape": d, "eps": eps}},
                {"gatedmlp": {"in_features": d, "intermediate_size": inter,
                              "activation": activation}}]},
            "post_norm_on_residual": post_norm_on_residual,
        }
        if has_post_norms:
            block["post_attn_norm"] = {"rmsnorm": {"normalized_shape": d,
                                                   "eps": eps}}
            block["post_mlp_norm"] = {"rmsnorm": {"normalized_shape": d,
                                                  "eps": eps}}
        layers.append({"transformerblock": block})
    layers += [
        {"rmsnorm": {"normalized_shape": d, "eps": eps}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": False}},
    ]
    final_cap = getattr(cfg, "final_logit_softcapping", None)
    if final_cap:
        # Gemma-2 caps the lm-head logits too (HF final_logit_softcapping)
        layers.append({"softcap": {"cap": float(final_cap)}})
    layers.append({"softmaxlast": {"dim": -1}})
    return layers


def _plus_one(arr):
    """RMSNorm weight offset: HF Gemma stores ``w`` and applies ``x*(1+w)``;
    our RMSNorm multiplies directly (reference: mappers.py:401,424-442)."""
    a = np.asarray(arr)
    return (a.astype(np.float32) + 1.0).astype(a.dtype)


def _map_gemma_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """Gemma HF keys → ours: QKV concat, +1 RMSNorm offset, KV-shared-layer
    copy from the reference layer, multimodal prefix (reference:
    mappers.py:356-448)."""
    prefix = "model"
    if any(k.startswith("model.language_model.") for k in sd):
        prefix = "model.language_model"
    model_type = getattr(config, "model_type", "gemma2") if config else "gemma2"
    cfg = _gemma_text_config(config) if config is not None else None
    has_post_norms = model_type != "gemma"
    num_kv_shared = int(getattr(cfg, "num_kv_shared_layers", 0) or 0) if cfg else 0
    layer_types = list(getattr(cfg, "layer_types", None) or []) if cfg else []

    out = {"layers.0.weight": sd[f"{prefix}.embed_tokens.weight"]}
    for i in range(n_layer):
        src = f"{prefix}.layers.{i}"
        dst = f"layers.{1 + i}"
        # KV-shared layers read K/V from the last same-type non-shared layer.
        kv_src_idx = i
        if num_kv_shared and i >= n_layer - num_kv_shared and layer_types:
            own_type = layer_types[i] if i < len(layer_types) else None
            for j in range(n_layer - num_kv_shared - 1, -1, -1):
                if j < len(layer_types) and layer_types[j] == own_type:
                    kv_src_idx = j
                    break
        kv_src = f"{prefix}.layers.{kv_src_idx}"
        out[f"{dst}.attn_block.1.weight"] = np.concatenate(
            [np.asarray(sd[f"{src}.self_attn.q_proj.weight"]),
             np.asarray(sd[f"{kv_src}.self_attn.k_proj.weight"]),
             np.asarray(sd[f"{kv_src}.self_attn.v_proj.weight"])], axis=0)
        out[f"{dst}.attn_block.0.weight"] = \
            _plus_one(sd[f"{src}.input_layernorm.weight"])
        if f"{src}.self_attn.q_norm.weight" in sd:
            # Gemma-3 per-head qk-norms (zero-centered like every gemma
            # RMSNorm); K comes from the KV-source layer on shared layers
            out[f"{dst}.attn_block.2.q_norm.weight"] = \
                _plus_one(sd[f"{src}.self_attn.q_norm.weight"])
            out[f"{dst}.attn_block.2.k_norm.weight"] = \
                _plus_one(sd[f"{kv_src}.self_attn.k_norm.weight"])
        out[f"{dst}.attn_block.3.weight"] = sd[f"{src}.self_attn.o_proj.weight"]
        if has_post_norms:
            out[f"{dst}.post_attn_norm.weight"] = \
                _plus_one(sd[f"{src}.post_attention_layernorm.weight"])
            out[f"{dst}.mlp_block.0.weight"] = \
                _plus_one(sd[f"{src}.pre_feedforward_layernorm.weight"])
            out[f"{dst}.post_mlp_norm.weight"] = \
                _plus_one(sd[f"{src}.post_feedforward_layernorm.weight"])
        else:
            # gemma1: the post-attention norm IS the pre-MLP norm.
            out[f"{dst}.mlp_block.0.weight"] = \
                _plus_one(sd[f"{src}.post_attention_layernorm.weight"])
        for proj in ("gate_proj", "up_proj", "down_proj"):
            out[f"{dst}.mlp_block.1.{proj}.weight"] = \
                sd[f"{src}.mlp.{proj}.weight"]
    out[f"layers.{1 + n_layer}.weight"] = _plus_one(sd[f"{prefix}.norm.weight"])
    out[f"layers.{2 + n_layer}.weight"] = sd.get(
        "lm_head.weight", sd[f"{prefix}.embed_tokens.weight"])
    return out


# ---------------------------------------------------------------------------
# Llama family (beyond reference parity: mappers.py covers GPT-2 + Gemma
# only; Llama/Mistral/Qwen2 reuse the same GQA+RoPE+RMSNorm+GatedMLP
# modules with pre-norm blocks, no +1 norm offset and no embedding scale)
# ---------------------------------------------------------------------------

_LLAMA_FAMILY = ("llama", "mistral", "mixtral", "phi3", "qwen2", "qwen3",
                 "qwen2_moe")


def _llama_text_config(config):
    get = getattr(config, "get_text_config", None)
    return get() if callable(get) else config


def _llama_moe_entry(model_type: str, cfg, d: int, n: int,
                     activation: str) -> dict:
    """Sparse-MoE MLP entry for the llama family.

    Mixtral: softmax over ALL experts → top-k → renormalize; dense
    dispatch reproduces HF bit-for-bit.  The aux coefficient is rescaled
    toward HF's load_balancing_loss_func (ONE loss from fractions pooled
    across layers with top-k-summed slots): coef × top_k / n_layers
    matches the coefficient SCALE; the per-layer-vs-pooled structural
    difference remains — the Switch formulation, not a bug.

    Qwen2-MoE: fine-grained experts with ``norm_topk_prob`` (default
    False — raw softmax mass on the selected experts) plus an always-on
    shared expert behind a sigmoid token gate.  Non-default
    ``decoder_sparse_step``/``mlp_only_layers`` (dense layers mixed into
    the stack) are refused loudly — importing them as sparse would be
    wrong math.
    """
    if model_type == "qwen2_moe":
        if int(getattr(cfg, "decoder_sparse_step", 1) or 1) != 1 or                 list(getattr(cfg, "mlp_only_layers", []) or []):
            raise ValueError(
                "qwen2_moe with decoder_sparse_step != 1 or non-empty "
                "mlp_only_layers (dense layers mixed into the stack) is "
                "not supported")
        return {"moe": {
            "in_features": d,
            "intermediate_size": int(cfg.moe_intermediate_size),
            "num_experts": int(cfg.num_experts),
            "top_k": int(cfg.num_experts_per_tok),
            "activation": activation,
            "norm_topk": bool(getattr(cfg, "norm_topk_prob", False)),
            "shared_expert_size":
                int(cfg.shared_expert_intermediate_size),
            "aux_loss_coef": (
                float(getattr(cfg, "router_aux_loss_coef", 0.0) or 0.0)
                * int(cfg.num_experts_per_tok) / n)}}
    return {"moe": {"in_features": d,
                    "intermediate_size": int(cfg.intermediate_size),
                    "num_experts": int(cfg.num_local_experts),
                    "top_k": int(cfg.num_experts_per_tok),
                    "activation": activation,
                    "aux_loss_coef": (
                        float(getattr(cfg, "router_aux_loss_coef",
                                      0.0) or 0.0)
                        * int(cfg.num_experts_per_tok) / n)}}


def _llama_biases(model_type: str, cfg) -> tuple[bool, bool]:
    """(qkv_bias, o_bias).  Qwen2 hardcodes qkv bias on / o bias off in its
    attention module; Llama/Mistral follow ``attention_bias`` (default
    False) for all four projections."""
    if model_type in ("qwen2", "qwen2_moe"):
        return True, False
    bias = bool(getattr(cfg, "attention_bias", False) or False)
    return bias, bias


def _llama_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """Llama/Mistral/Qwen2/Qwen3 HF config → layer DSL.

    ``rope_scaling`` with ``rope_type='llama3'`` (Llama 3.1+) is applied as
    an inverse-frequency rescale (ops/attention.rope_cos_sin); other active
    types (yarn, dynamic, ...) raise — importing with them ignored would
    produce silently wrong logits.  A sliding window (Mistral) becomes real
    windowed attention (ops/attention window masks) — beyond the reference,
    which keeps all attention full causal (mappers.py:224-228).
    """
    model_type = getattr(config, "model_type", "llama")
    cfg = _llama_text_config(config)
    scaling = getattr(cfg, "rope_scaling", None) or None
    if scaling:
        rope_type = (scaling.get("rope_type") or scaling.get("type")
                     or "default")
        if rope_type == "default":
            scaling = None
        elif rope_type != "llama3":
            raise ValueError(
                f"rope_scaling {rope_type!r} is not supported; importing "
                "would produce wrong logits")
        else:
            scaling = {"rope_type": "llama3", **{
                k: float(scaling[k]) for k in
                ("factor", "low_freq_factor", "high_freq_factor",
                 "original_max_position_embeddings") if k in scaling}}
    window = getattr(cfg, "sliding_window", None)
    window = int(window) if window else None
    # Per-layer gating: Qwen2's use_sliding_window/max_window_layers (and
    # any llama-family config with layer_types) window only the layers HF
    # marks 'sliding_attention'; Mistral windows every layer.
    layer_types = list(getattr(cfg, "layer_types", None) or [])

    def window_for(i: int):
        if window is None:
            return None
        if layer_types:
            lt = layer_types[i] if i < len(layer_types) else "full_attention"
            return window if lt == "sliding_attention" else None
        return window
    d = int(cfg.hidden_size)
    n = int(n_layer_override if n_layer_override else cfg.num_hidden_layers)
    heads = int(cfg.num_attention_heads)
    kv = int(getattr(cfg, "num_key_value_heads", None) or heads)
    hd = int(getattr(cfg, "head_dim", None) or d // heads)
    vocab = int(cfg.vocab_size)
    eps = float(getattr(cfg, "rms_norm_eps", 1e-6))
    rope = float(getattr(cfg, "rope_theta", 10000.0) or 10000.0)
    attn_drop = float(getattr(cfg, "attention_dropout", 0.0) or 0.0)
    activation = getattr(cfg, "hidden_act", "silu")
    qkv_bias, o_bias = _llama_biases(model_type, cfg)
    if getattr(cfg, "mlp_bias", False):
        raise ValueError("mlp_bias=True Llama checkpoints are not supported")

    attn_args = {"num_heads": heads, "num_kv_heads": kv, "rope_theta": rope,
                 "head_dim": hd, "dropout": attn_drop}
    rope_pct = float(getattr(cfg, "partial_rotary_factor", 1.0) or 1.0)
    if rope_pct < 1.0:
        # Phi-3-family configs (e.g. Phi-4-mini ships model_type 'phi3'
        # with 0.75) rotate only the first pct of each head's dims —
        # ignoring it would import with silently wrong logits.
        attn_args["rope_pct"] = rope_pct
    if scaling:
        attn_args["rope_scaling"] = scaling
    if model_type == "qwen3":
        # Qwen3 RMS-normalizes q and k per head before RoPE with learned
        # (head_dim,) weights (HF Qwen3Attention q_norm/k_norm).
        attn_args.update(qk_norm=True, qk_norm_eps=eps)
    layers: list[dict] = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
         "normal": {"mean": 0.0, "std": 0.02}},
    ]
    for i in range(n):
        layer_attn = dict(attn_args)
        if window_for(i) is not None:
            layer_attn["sliding_window"] = window_for(i)
        layers.append({"transformerblock": {
            "attn_block": {"sequential": [
                {"rmsnorm": {"normalized_shape": d, "eps": eps}},
                {"linear": {"in_features": d,
                            "out_features": (heads + 2 * kv) * hd,
                            "bias": qkv_bias}},
                {"attention": layer_attn},
                {"linear": {"in_features": heads * hd, "out_features": d,
                            "bias": o_bias}}]},
            "mlp_block": {"sequential": [
                {"rmsnorm": {"normalized_shape": d, "eps": eps}},
                # Mixtral: sparse MoE MLP.  Routing math matches our
                # module exactly (HF MixtralSparseMoeBlock: softmax over
                # ALL experts -> top-k -> renormalize); dense dispatch
                # reproduces it bit-for-bit, capacity dispatch stays an
                # opt-in.  The aux coefficient is rescaled toward HF's
                # load_balancing_loss_func: HF computes ONE loss from
                # fractions POOLED across all layers with top-k-summed
                # slots (uniform minimum top_k); our Switch form divides
                # by top_k (minimum 1) and applies per layer.  coef ×
                # top_k / n_layers matches the coefficient SCALE (equal
                # when routing statistics are layer-uniform); the
                # per-layer-vs-pooled structural difference remains — the
                # Switch formulation, not a bug.
                (_llama_moe_entry(model_type, cfg, d, n, activation)
                 if model_type in ("mixtral", "qwen2_moe") else
                 {"gatedmlp": {"in_features": d,
                               "intermediate_size":
                                   int(cfg.intermediate_size),
                               "activation": activation}})]},
            "post_norm_on_residual": False,
        }})
    layers += [
        {"rmsnorm": {"normalized_shape": d, "eps": eps}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": False}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _ouro_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """Ouro (ByteDance, ``model_type`` ``ouro``) HF config → layer DSL: the
    looped stack of ``presets.ouro_custom`` at the config's sizes.  What
    the config does not say (sandwich norms, the gate's bias, the entropy
    weight) is the preset's."""
    from penroz_tpu.models import presets
    if (getattr(config, "use_sliding_window", False)
            and getattr(config, "sliding_window", None)):
        raise ValueError("ouro with a sliding window is not supported")
    if getattr(config, "rope_scaling", None):
        raise ValueError("ouro with rope_scaling is not supported")
    d = int(config.hidden_size)
    heads = int(config.num_attention_heads)
    kv = int(getattr(config, "num_key_value_heads", None) or heads)
    if kv != heads:
        raise ValueError("ouro with grouped K/V heads is not supported")
    if getattr(config, "hidden_act", "silu") != "silu":
        raise ValueError("ouro with an activation other than silu is not "
                         "supported")
    return presets.ouro_custom(
        d=d, heads=heads,
        head_dim=int(getattr(config, "head_dim", None) or d // heads),
        intermediate=int(config.intermediate_size),
        depth=int(n_layer_override if n_layer_override
                  else config.num_hidden_layers),
        steps=int(getattr(config, "total_ut_steps", 4)),
        vocab=int(config.vocab_size),
        rope_theta=float(getattr(config, "rope_theta", 1e6) or 1e6),
        eps=float(getattr(config, "rms_norm_eps", 1e-6)))


def _gelu_entry(act: str, family: str) -> dict:
    """HF activation string → DSL entry (shared by the NeoX/Phi/GPT-J
    builders; GPT-2 keeps its own historical mapping)."""
    if act in ("gelu_new", "gelu_pytorch_tanh", "gelu_fast"):
        return {"gelu": {"approximate": "tanh"}}
    if act == "gelu":
        return {"gelu": {}}
    if act == "relu":
        return {"relu": {}}
    raise ValueError(f"Unsupported {family} activation: {act!r}")


def _neox_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """GPT-NeoX/Pythia HF config → layer DSL.

    Two capabilities beyond the other families: the ``parallelresidual``
    container (``use_parallel_residual``: attention and MLP branches both
    read the pre-block activations, HF ``modeling_gpt_neox`` forward) and
    partial rotary (``rotary_pct`` → the attention module's ``rope_pct``).
    ``use_parallel_residual=False`` checkpoints get the ordinary
    sequential-residual block.
    """
    cfg = _llama_text_config(config)
    scaling = getattr(cfg, "rope_scaling", None) or None
    if scaling and (scaling.get("rope_type") or scaling.get("type")
                    or "default") != "default":
        # Same guard as the llama builder: importing with an active scaling
        # silently ignored would produce wrong logits.
        raise ValueError(
            f"gpt_neox rope_scaling {scaling!r} is not supported; importing "
            "would produce wrong logits")
    d = int(cfg.hidden_size)
    n = int(n_layer_override if n_layer_override else cfg.num_hidden_layers)
    heads = int(cfg.num_attention_heads)
    vocab = int(cfg.vocab_size)
    eps = float(getattr(cfg, "layer_norm_eps", 1e-5))
    rope = float(getattr(cfg, "rope_theta", None)
                 or getattr(cfg, "rotary_emb_base", None) or 10000.0)
    rope_pct = getattr(cfg, "rotary_pct", None)
    rope_pct = 0.25 if rope_pct is None else float(rope_pct)
    attn_bias = bool(getattr(cfg, "attention_bias", True))
    attn_drop = float(getattr(cfg, "attention_dropout", 0.0) or 0.0)
    hidden_drop = float(getattr(cfg, "hidden_dropout", 0.0) or 0.0)
    act_entry = _gelu_entry(getattr(cfg, "hidden_act", "gelu"), "gpt_neox")
    parallel = bool(getattr(cfg, "use_parallel_residual", True))
    inter = int(getattr(cfg, "intermediate_size", None) or 4 * d)

    attn_args = {"num_heads": heads, "dropout": attn_drop}
    if rope_pct > 0.0:
        # rotary_pct=0.0 is a valid HF config (rotary_ndims=0, rope is a
        # no-op) — omit rope entirely rather than rotating dims the torch
        # original never rotated.
        attn_args.update(rope_theta=rope, rope_pct=rope_pct)
    layers: list[dict] = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
         "normal": {"mean": 0.0, "std": 0.02}},
    ]
    for _ in range(n):
        attn_branch = {"sequential": [
            {"layernorm": {"normalized_shape": d, "eps": eps}},
            {"linear": {"in_features": d, "out_features": 3 * d,
                        "bias": attn_bias}},
            {"attention": dict(attn_args)},
            {"linear": {"in_features": d, "out_features": d,
                        "bias": attn_bias}}]
            + ([{"dropout": {"p": hidden_drop}}] if hidden_drop else [])}
        mlp_branch = {"sequential": [
            {"layernorm": {"normalized_shape": d, "eps": eps}},
            {"linear": {"in_features": d, "out_features": inter}},
            act_entry,
            {"linear": {"in_features": inter, "out_features": d}}]
            + ([{"dropout": {"p": hidden_drop}}] if hidden_drop else [])}
        container = "parallelresidual" if parallel else "residual"
        layers.append({container: [attn_branch, mlp_branch]})
    layers += [
        {"layernorm": {"normalized_shape": d, "eps": eps}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": False}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _phi_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """Phi-1/1.5/2 HF config → layer DSL.

    Phi blocks are parallel-residual with ONE shared input LayerNorm
    feeding both branches (HF ``modeling_phi`` forward: attention and MLP
    both read ``input_layernorm(x)`` and their outputs sum onto the
    residual — cf. NeoX, where each branch carries its own norm), so the
    block nests as ``residual([sequential([ln, summation([attn, mlp])])])``.
    Partial rotary via ``partial_rotary_factor`` (default 0.5), biases on
    every projection, biased final lm_head, LayerNorm (not RMSNorm).
    """
    cfg = _llama_text_config(config)
    if getattr(cfg, "qk_layernorm", False):
        raise ValueError("qk_layernorm Phi checkpoints are not supported")
    if getattr(cfg, "tie_word_embeddings", False):
        # HF drops tied weights on save, and the biased head the phi DSL
        # builds has no tied-bias analogue — reject with a clear message
        # instead of a KeyError mid-import.
        raise ValueError("tie_word_embeddings=True phi checkpoints are "
                         "not supported")
    d = int(cfg.hidden_size)
    n = int(n_layer_override if n_layer_override else cfg.num_hidden_layers)
    heads = int(cfg.num_attention_heads)
    kv = int(getattr(cfg, "num_key_value_heads", None) or heads)
    hd = d // heads
    vocab = int(cfg.vocab_size)
    eps = float(getattr(cfg, "layer_norm_eps", 1e-5))
    rope = float(getattr(cfg, "rope_theta", 10000.0) or 10000.0)
    rope_pct = getattr(cfg, "partial_rotary_factor", None)
    rope_pct = 0.5 if rope_pct is None else float(rope_pct)
    attn_drop = float(getattr(cfg, "attention_dropout", 0.0) or 0.0)
    resid_drop = float(getattr(cfg, "resid_pdrop", 0.0) or 0.0)
    embd_drop = float(getattr(cfg, "embd_pdrop", 0.0) or 0.0)
    inter = int(getattr(cfg, "intermediate_size", None) or 4 * d)
    act_entry = _gelu_entry(getattr(cfg, "hidden_act", "gelu_new"), "phi")

    attn_args = {"num_heads": heads, "num_kv_heads": kv, "dropout": attn_drop}
    if rope_pct > 0.0:
        # partial_rotary_factor=0.0 disables rope entirely (rotary_ndims=0
        # in the torch original) — rotating dims it never rotated would
        # silently diverge the logits.
        attn_args.update(rope_theta=rope, rope_pct=rope_pct)
    tail_drop = [{"dropout": {"p": resid_drop}}] if resid_drop else []
    layers: list[dict] = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
         "normal": {"mean": 0.0, "std": 0.02}},
    ]
    if embd_drop:
        layers.append({"dropout": {"p": embd_drop}})
    for _ in range(n):
        attn_branch = {"sequential": [
            {"linear": {"in_features": d,
                        "out_features": (heads + 2 * kv) * hd}},
            {"attention": dict(attn_args)},
            {"linear": {"in_features": heads * hd, "out_features": d}}]
            + tail_drop}
        mlp_branch = {"sequential": [
            {"linear": {"in_features": d, "out_features": inter}},
            act_entry,
            {"linear": {"in_features": inter, "out_features": d}}]
            + tail_drop}
        layers.append({"residual": [{"sequential": [
            {"layernorm": {"normalized_shape": d, "eps": eps}},
            {"summation": [attn_branch, mlp_branch]}]}]})
    layers += [
        {"layernorm": {"normalized_shape": d, "eps": eps}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": True}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _map_phi_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """Phi HF keys → ours: QKV (+bias) concat like llama, the block's
    single input_layernorm lands inside the residual container
    (``layers.{i}.0.0``), branch projections under the summation
    (``layers.{i}.0.1.{branch}.{item}``), biased final head kept."""
    cfg = _llama_text_config(config)
    base = 1 + (1 if float(getattr(cfg, "embd_pdrop", 0.0) or 0.0) else 0)
    out = {"layers.0.weight": sd["model.embed_tokens.weight"]}
    for i in range(n_layer):
        src = f"model.layers.{i}"
        dst = f"layers.{base + i}.0"
        _concat_qkv(sd, src, out, f"{dst}.1.0.0")
        for name in ("weight", "bias"):
            out[f"{dst}.0.{name}"] = sd[f"{src}.input_layernorm.{name}"]
            out[f"{dst}.1.0.2.{name}"] = sd[f"{src}.self_attn.dense.{name}"]
            out[f"{dst}.1.1.0.{name}"] = sd[f"{src}.mlp.fc1.{name}"]
            out[f"{dst}.1.1.2.{name}"] = sd[f"{src}.mlp.fc2.{name}"]
    for name in ("weight", "bias"):
        out[f"layers.{base + n_layer}.{name}"] = \
            sd[f"model.final_layernorm.{name}"]
        out[f"layers.{base + n_layer + 1}.{name}"] = sd[f"lm_head.{name}"]
    return out


def _olmo2_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """OLMo-2 HF config → layer DSL.

    OLMo-2 blocks are POST-norm only (HF ``modeling_olmo2``: no input
    norm; ``post_attention_layernorm`` wraps the attention branch output
    and ``post_feedforward_layernorm`` the MLP's, each BEFORE the residual
    add), with flat q/k RMS normalization — ``Olmo2Attention`` normalizes
    the whole (H·hd) projection before the head split (``qk_norm_scope=
    'flat'``, unlike Qwen3's per-head norm).  Expressed with the generic
    residual container: each branch ends in its rmsnorm.
    """
    cfg = _llama_text_config(config)
    scaling = getattr(cfg, "rope_scaling", None) or None
    if scaling and (scaling.get("rope_type") or scaling.get("type")
                    or "default") != "default":
        # Same guard as the llama/neox builders: importing with an active
        # scaling silently ignored would produce wrong logits.
        raise ValueError(
            f"olmo2 rope_scaling {scaling!r} is not supported; importing "
            "would produce wrong logits")
    d = int(cfg.hidden_size)
    n = int(n_layer_override if n_layer_override else cfg.num_hidden_layers)
    heads = int(cfg.num_attention_heads)
    kv = int(getattr(cfg, "num_key_value_heads", None) or heads)
    hd = d // heads
    vocab = int(cfg.vocab_size)
    eps = float(getattr(cfg, "rms_norm_eps", 1e-6))
    rope = float(getattr(cfg, "rope_theta", 10000.0) or 10000.0)
    attn_drop = float(getattr(cfg, "attention_dropout", 0.0) or 0.0)
    bias = bool(getattr(cfg, "attention_bias", False) or False)
    inter = int(cfg.intermediate_size)
    activation = getattr(cfg, "hidden_act", "silu")

    layers: list[dict] = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
         "normal": {"mean": 0.0, "std": 0.02}},
    ]
    for _ in range(n):
        layers.append({"residual": [
            {"sequential": [
                {"linear": {"in_features": d,
                            "out_features": (heads + 2 * kv) * hd,
                            "bias": bias}},
                {"attention": {"num_heads": heads, "num_kv_heads": kv,
                               "rope_theta": rope, "head_dim": hd,
                               "dropout": attn_drop, "qk_norm": True,
                               "qk_norm_scope": "flat",
                               "qk_norm_fp32_weight": True,
                               "qk_norm_eps": eps}},
                {"linear": {"in_features": heads * hd, "out_features": d,
                            "bias": bias}},
                {"rmsnorm": {"normalized_shape": d, "eps": eps}}]},
            {"sequential": [
                {"gatedmlp": {"in_features": d, "intermediate_size": inter,
                              "activation": activation}},
                {"rmsnorm": {"normalized_shape": d, "eps": eps}}]}]})
    layers += [
        {"rmsnorm": {"normalized_shape": d, "eps": eps}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": False}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _olmo_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """OLMo v1 HF config → layer DSL.

    Llama-like pre-norm blocks with two quirks: NON-PARAMETRIC LayerNorm
    (HF ``OlmoLayerNorm``: elementwise_affine=False, no weights at all)
    and optional ``clip_qkv`` — the fused QKV projection output clamps to
    ±clip before attention (a ``clamp`` DSL entry).
    """
    cfg = _llama_text_config(config)
    scaling = getattr(cfg, "rope_scaling", None) or None
    if scaling and (scaling.get("rope_type") or scaling.get("type")
                    or "default") != "default":
        raise ValueError(
            f"olmo rope_scaling {scaling!r} is not supported; importing "
            "would produce wrong logits")
    d = int(cfg.hidden_size)
    n = int(n_layer_override if n_layer_override else cfg.num_hidden_layers)
    heads = int(cfg.num_attention_heads)
    kv = int(getattr(cfg, "num_key_value_heads", None) or heads)
    hd = d // heads
    vocab = int(cfg.vocab_size)
    rope = float(getattr(cfg, "rope_theta", 10000.0) or 10000.0)
    attn_drop = float(getattr(cfg, "attention_dropout", 0.0) or 0.0)
    bias = bool(getattr(cfg, "attention_bias", False) or False)
    clip = getattr(cfg, "clip_qkv", None)
    inter = int(cfg.intermediate_size)
    activation = getattr(cfg, "hidden_act", "silu")
    ln = {"layernorm": {"normalized_shape": d, "eps": 1e-5,
                        "elementwise_affine": False}}

    layers: list[dict] = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
         "normal": {"mean": 0.0, "std": 0.02}},
    ]
    for _ in range(n):
        attn_seq = [dict(ln),
                    {"linear": {"in_features": d,
                                "out_features": (heads + 2 * kv) * hd,
                                "bias": bias}}]
        if clip is not None:
            attn_seq.append({"clamp": {"min": -float(clip),
                                       "max": float(clip)}})
        attn_seq += [{"attention": {"num_heads": heads, "num_kv_heads": kv,
                                    "rope_theta": rope, "head_dim": hd,
                                    "dropout": attn_drop}},
                     {"linear": {"in_features": heads * hd,
                                 "out_features": d, "bias": bias}}]
        layers.append({"residual": [
            {"sequential": attn_seq},
            {"sequential": [dict(ln),
                            {"gatedmlp": {"in_features": d,
                                          "intermediate_size": inter,
                                          "activation": activation}}]}]})
    layers += [
        dict(ln),
        {"linear": {"in_features": d, "out_features": vocab, "bias": False}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _stablelm_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """StableLM HF config → layer DSL: the llama block structure with
    LayerNorm (weight+bias) instead of RMSNorm, partial rotary
    (``partial_rotary_factor``, default 0.25), gated silu MLP, optional
    qkv bias (``use_qkv_bias``), untied-or-tied head.
    ``use_parallel_residual`` / ``qk_layernorm`` variants are refused
    rather than silently mis-structured."""
    cfg = _llama_text_config(config)
    if getattr(cfg, "use_parallel_residual", False):
        raise ValueError("use_parallel_residual StableLM checkpoints are "
                         "not supported")
    if getattr(cfg, "qk_layernorm", False):
        raise ValueError("qk_layernorm StableLM checkpoints are not "
                         "supported")
    scaling = getattr(cfg, "rope_scaling", None) or None
    if scaling and (scaling.get("rope_type") or scaling.get("type")
                    or "default") != "default":
        raise ValueError(
            f"stablelm rope_scaling {scaling!r} is not supported; "
            "importing would produce wrong logits")
    d = int(cfg.hidden_size)
    n = int(n_layer_override if n_layer_override else cfg.num_hidden_layers)
    heads = int(cfg.num_attention_heads)
    kv = int(getattr(cfg, "num_key_value_heads", None) or heads)
    hd = d // heads
    vocab = int(cfg.vocab_size)
    eps = float(getattr(cfg, "layer_norm_eps", 1e-5))
    rope = float(getattr(cfg, "rope_theta", 10000.0) or 10000.0)
    rope_pct = getattr(cfg, "partial_rotary_factor", None)
    rope_pct = 0.25 if rope_pct is None else float(rope_pct)
    attn_drop = float(getattr(cfg, "attention_dropout", 0.0) or 0.0)
    qkv_bias = bool(getattr(cfg, "use_qkv_bias", False))
    inter = int(cfg.intermediate_size)
    activation = getattr(cfg, "hidden_act", "silu")

    attn_args = {"num_heads": heads, "num_kv_heads": kv, "head_dim": hd,
                 "dropout": attn_drop}
    if rope_pct > 0.0:
        attn_args.update(rope_theta=rope, rope_pct=rope_pct)
    layers: list[dict] = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
         "normal": {"mean": 0.0, "std": 0.02}},
    ]
    for _ in range(n):
        layers.append({"transformerblock": {
            "attn_block": {"sequential": [
                {"layernorm": {"normalized_shape": d, "eps": eps}},
                {"linear": {"in_features": d,
                            "out_features": (heads + 2 * kv) * hd,
                            "bias": qkv_bias}},
                {"attention": dict(attn_args)},
                {"linear": {"in_features": heads * hd, "out_features": d,
                            "bias": False}}]},
            "mlp_block": {"sequential": [
                {"layernorm": {"normalized_shape": d, "eps": eps}},
                {"gatedmlp": {"in_features": d, "intermediate_size": inter,
                              "activation": activation}}]},
            "post_norm_on_residual": False,
        }})
    layers += [
        {"layernorm": {"normalized_shape": d, "eps": eps}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": False}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _gptj_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """GPT-J HF config → layer DSL.

    Parallel attention+MLP branches sharing ONE ``ln_1`` per block (the
    Phi nesting: ``residual([sequential([ln, summation([attn, mlp])])])``
    — HF ``modeling_gptj`` forward sums both branch outputs onto the
    residual), bias-free q/k/v/out projections, biased fc_in/fc_out MLP
    with gelu_new, biased lm_head, and partial INTERLEAVED rotary
    (``rotary_dim`` dims, rotate-every-two pairs).  The interleave is
    handled entirely at import: the mapper de-interleaves each head's
    q/k projection rows into the half-split layout our rope uses — q·k
    dot products are invariant to a consistent feature permutation, so
    no runtime rope variant is needed.
    """
    cfg = _llama_text_config(config)
    if getattr(cfg, "tie_word_embeddings", False):
        # HF drops tied weights on save and the biased head the gptj DSL
        # builds has no tied analogue — reject with a clear message.
        raise ValueError("tie_word_embeddings=True gptj checkpoints are "
                         "not supported")
    d = int(cfg.hidden_size if hasattr(cfg, "hidden_size") else cfg.n_embd)
    n = int(n_layer_override if n_layer_override
            else getattr(cfg, "num_hidden_layers", None) or cfg.n_layer)
    heads = int(getattr(cfg, "num_attention_heads", None) or cfg.n_head)
    hd = d // heads
    vocab = int(cfg.vocab_size)
    eps = float(getattr(cfg, "layer_norm_epsilon", 1e-5))
    rotary_dim = int(getattr(cfg, "rotary_dim", None) or hd)
    attn_drop = float(getattr(cfg, "attn_pdrop", 0.0) or 0.0)
    resid_drop = float(getattr(cfg, "resid_pdrop", 0.0) or 0.0)
    embd_drop = float(getattr(cfg, "embd_pdrop", 0.0) or 0.0)
    inter = int(getattr(cfg, "n_inner", None) or 4 * d)
    act_entry = _gelu_entry(
        getattr(cfg, "activation_function", "gelu_new"), "gptj")

    attn_args = {"num_heads": heads, "dropout": attn_drop,
                 "rope_theta": 10000.0, "rope_dim": rotary_dim}
    tail_drop = [{"dropout": {"p": resid_drop}}] if resid_drop else []
    layers: list[dict] = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
         "normal": {"mean": 0.0, "std": 0.02}},
    ]
    if embd_drop:
        layers.append({"dropout": {"p": embd_drop}})
    for _ in range(n):
        attn_branch = {"sequential": [
            {"linear": {"in_features": d, "out_features": 3 * d,
                        "bias": False}},
            {"attention": dict(attn_args)},
            {"linear": {"in_features": d, "out_features": d,
                        "bias": False}}] + tail_drop}
        mlp_branch = {"sequential": [
            {"linear": {"in_features": d, "out_features": inter}},
            act_entry,
            {"linear": {"in_features": inter, "out_features": d}}]
            + tail_drop}
        layers.append({"residual": [{"sequential": [
            {"layernorm": {"normalized_shape": d, "eps": eps}},
            {"summation": [attn_branch, mlp_branch]}]}]})
    layers += [
        {"layernorm": {"normalized_shape": d, "eps": eps}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": True}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _gptj_deinterleave(w: np.ndarray, heads: int, rotary_dim: int
                       ) -> np.ndarray:
    """Per head, reorder the first ``rotary_dim`` projection rows from
    GPT-J's interleaved pair layout (x0,x1),(x2,x3)… to the half-split
    layout (x_even… then x_odd…) our rope rotates; pass-through rows stay
    put.  Works for (d, d) weights (row-major per-head blocks)."""
    w = np.asarray(w)
    hd = w.shape[0] // heads
    out = w.copy()
    for h in range(heads):
        base = h * hd
        rot = w[base:base + rotary_dim]
        out[base:base + rotary_dim] = np.concatenate([rot[0::2], rot[1::2]])
    return out


def _falcon_arch(cfg) -> tuple[bool, int]:
    """(new_decoder_architecture, effective num_kv_heads) — HF
    ``FalconAttention``: kv heads are ``num_kv_heads`` under the new
    architecture (or when multi_query is off), else 1 (MQA)."""
    new_arch = bool(getattr(cfg, "new_decoder_architecture", False))
    if new_arch or not getattr(cfg, "multi_query", True):
        kv = int(getattr(cfg, "num_kv_heads", None)
                 or cfg.num_attention_heads)
    else:
        kv = 1
    return new_arch, kv


def _falcon_dsl_from_config(config, n_layer_override=None) -> list[dict]:
    """Falcon HF config → layer DSL, both decoder architectures:

    - 40B-style (``new_decoder_architecture``): two norms feed PARALLEL
      attention/MLP branches (``ln_attn``/``ln_mlp``) — the NeoX
      ``parallelresidual`` container; GQA via ``num_kv_heads``.
    - 7B-style (``multi_query`` + ``parallel_attn``): ONE
      ``input_layernorm`` shared by both parallel branches (the Phi
      nesting) with MQA (1 kv head).

    Full NeoX-style rotary, bias-free projections (``bias``), erf gelu
    MLP, tied head by default.  Alibi, non-rotary, sequential
    (``parallel_attn=False``) and single-ln-new-arch
    (``num_ln_in_parallel_attn=1``) variants are refused loudly.
    """
    cfg = _llama_text_config(config)
    if getattr(cfg, "alibi", False):
        # falcon-rw shape: ALiBi + sequential pre-LN blocks + per-head-
        # interleaved fused QKV (BLOOM's layout).  Other alibi combos
        # (parallel branches, MQA/GQA) have no released checkpoints —
        # refused rather than guessed.
        if (getattr(cfg, "new_decoder_architecture", False)
                or getattr(cfg, "multi_query", True)
                or getattr(cfg, "parallel_attn", True)):
            raise ValueError(
                "alibi Falcon is supported only in the falcon-rw shape "
                "(multi_query=False, parallel_attn=False, classic "
                "decoder architecture)")
        return _falcon_rw_dsl(cfg, n_layer_override)
    scaling = getattr(cfg, "rope_scaling", None) or None
    if scaling and (scaling.get("rope_type") or scaling.get("type")
                    or "default") != "default":
        raise ValueError(
            f"falcon rope_scaling {scaling!r} is not supported; importing "
            "would produce wrong logits")
    if not getattr(cfg, "rotary", True):
        raise ValueError("non-rotary Falcon checkpoints are not supported")
    new_arch, kv = _falcon_arch(cfg)
    if not new_arch and not getattr(cfg, "parallel_attn", True):
        raise ValueError("sequential (parallel_attn=False) Falcon "
                         "checkpoints are not supported")
    if new_arch and getattr(cfg, "num_ln_in_parallel_attn", None) == 1:
        raise ValueError("num_ln_in_parallel_attn=1 Falcon checkpoints "
                         "are not supported")
    d = int(cfg.hidden_size)
    n = int(n_layer_override if n_layer_override else cfg.num_hidden_layers)
    heads = int(cfg.num_attention_heads)
    hd = d // heads
    vocab = int(cfg.vocab_size)
    eps = float(getattr(cfg, "layer_norm_epsilon", 1e-5))
    rope = float(getattr(cfg, "rope_theta", 10000.0) or 10000.0)
    attn_drop = float(getattr(cfg, "attention_dropout", 0.0) or 0.0)
    hidden_drop = float(getattr(cfg, "hidden_dropout", 0.0) or 0.0)
    bias = bool(getattr(cfg, "bias", False))
    ffn = int(getattr(cfg, "ffn_hidden_size", None) or 4 * d)
    act_entry = _gelu_entry(getattr(cfg, "activation", "gelu"), "falcon")

    attn_args = {"num_heads": heads, "num_kv_heads": kv, "head_dim": hd,
                 "dropout": attn_drop, "rope_theta": rope}
    tail_drop = [{"dropout": {"p": hidden_drop}}] if hidden_drop else []
    qkv = {"linear": {"in_features": d,
                      "out_features": (heads + 2 * kv) * hd, "bias": bias}}
    dense = {"linear": {"in_features": heads * hd, "out_features": d,
                        "bias": bias}}
    h4h = {"linear": {"in_features": d, "out_features": ffn, "bias": bias}}
    fh = {"linear": {"in_features": ffn, "out_features": d, "bias": bias}}
    ln = {"layernorm": {"normalized_shape": d, "eps": eps}}

    layers: list[dict] = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
         "normal": {"mean": 0.0, "std": 0.02}},
    ]
    for _ in range(n):
        if new_arch:
            layers.append({"parallelresidual": [
                {"sequential": [dict(ln), qkv, {"attention": dict(attn_args)},
                                dense] + tail_drop},
                {"sequential": [dict(ln), h4h, dict(act_entry), fh]
                 + tail_drop}]})
        else:
            layers.append({"residual": [{"sequential": [
                dict(ln),
                {"summation": [
                    {"sequential": [qkv, {"attention": dict(attn_args)},
                                    dense] + tail_drop},
                    {"sequential": [h4h, dict(act_entry), fh]
                     + tail_drop}]},
            ]}]})
    layers += [
        dict(ln),
        {"linear": {"in_features": d, "out_features": vocab, "bias": False}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _falcon_defuse_qkv(w: np.ndarray, heads: int, kv: int, new_arch: bool,
                       multi_query: bool) -> np.ndarray:
    """Falcon fused query_key_value → our [all q; all k; all v] layout.

    - new architecture: per-kv-group blocks [q_0..q_{g-1}, k, v];
    - old MQA: already [all q, k, v] (kv=1) — identity;
    - old non-MQA (falcon-rw): per-head [q, k, v] — NeoX interleave.
    Works for weights (rows, d) and biases (rows,)."""
    w = np.asarray(w)
    if new_arch:
        group = heads // kv
        hd = w.shape[0] // (kv * (group + 2))
        blk = w.reshape((kv, group + 2, hd) + w.shape[1:])
        q = blk[:, :group].reshape((heads * hd,) + w.shape[1:])
        k = blk[:, group].reshape((kv * hd,) + w.shape[1:])
        v = blk[:, group + 1].reshape((kv * hd,) + w.shape[1:])
        return np.concatenate([q, k, v])
    if multi_query:
        return w
    return _neox_deinterleave_qkv(w, heads)


def _falcon_rw_dsl(cfg, n_layer_override=None) -> list[dict]:
    """falcon-rw (RefinedWeb) config → layer DSL: ALiBi attention, the
    standard sequential pre-LN block, biased projections, exact-GELU
    MLPs — structurally BLOOM minus the embedding LayerNorm."""
    d = int(cfg.hidden_size)
    n = int(n_layer_override if n_layer_override else cfg.num_hidden_layers)
    heads = int(cfg.num_attention_heads)
    vocab = int(cfg.vocab_size)
    eps = float(getattr(cfg, "layer_norm_epsilon", 1e-5))
    attn_drop = float(getattr(cfg, "attention_dropout", 0.0) or 0.0)
    hidden_drop = float(getattr(cfg, "hidden_dropout", 0.0) or 0.0)
    bias = bool(getattr(cfg, "bias", False))
    ffn = int(getattr(cfg, "ffn_hidden_size", None) or 4 * d)
    act_entry = _gelu_entry(getattr(cfg, "activation", "gelu"), "falcon")

    layers: list[dict] = [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
         "normal": {"mean": 0.0, "std": 0.02}},
    ]
    for _ in range(n):
        layers.append({"residual": [
            {"sequential": [
                {"layernorm": {"normalized_shape": d, "eps": eps}},
                {"linear": {"in_features": d, "out_features": 3 * d,
                            "bias": bias},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                {"attention": {"num_heads": heads, "dropout": attn_drop,
                               "alibi": True}},
                {"linear": {"in_features": d, "out_features": d,
                            "bias": bias},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                {"dropout": {"p": hidden_drop}}]},
            {"sequential": [
                {"layernorm": {"normalized_shape": d, "eps": eps}},
                {"linear": {"in_features": d, "out_features": ffn,
                            "bias": bias},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                act_entry,
                {"linear": {"in_features": ffn, "out_features": d,
                            "bias": bias},
                 "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
                {"dropout": {"p": hidden_drop}}]}]})
    layers += [
        {"layernorm": {"normalized_shape": d, "eps": eps}},
        {"linear": {"in_features": d, "out_features": vocab, "bias": False},
         "normal": {"mean": 0.0, "std": 0.02}},
        {"softmaxlast": {"dim": -1}},
    ]
    return layers


def _deinterleave_per_head(arr, heads: int):
    """BLOOM/falcon fused-QKV de-interleave: rows grouped per head as
    ``[h0: q,k,v | h1: q,k,v | …]`` → our ``[all q | all k | all v]``."""
    a = np.asarray(arr)
    if a.ndim == 2:
        h3d, d_in = a.shape
        hd = h3d // 3 // heads
        return a.reshape(heads, 3, hd, d_in).transpose(1, 0, 2, 3) \
                .reshape(h3d, d_in)
    hd = a.shape[0] // 3 // heads
    return a.reshape(heads, 3, hd).transpose(1, 0, 2).reshape(-1)


def _map_falcon_rw_state_dict(sd: dict, n_layer: int, heads: int) -> dict:
    """falcon-rw HF keys → ours (sequential blocks, interleaved QKV)."""
    pfx = "transformer"
    out = {"layers.0.weight": sd[f"{pfx}.word_embeddings.weight"]}
    for i in range(n_layer):
        src = f"{pfx}.h.{i}"
        dst = f"layers.{1 + i}"
        out[f"{dst}.0.0.weight"] = sd[f"{src}.input_layernorm.weight"]
        out[f"{dst}.0.0.bias"] = sd[f"{src}.input_layernorm.bias"]
        qkv = f"{src}.self_attention.query_key_value"
        out[f"{dst}.0.1.weight"] = _deinterleave_per_head(
            sd[f"{qkv}.weight"], heads)
        if f"{qkv}.bias" in sd:
            out[f"{dst}.0.1.bias"] = _deinterleave_per_head(
                sd[f"{qkv}.bias"], heads)
        out[f"{dst}.0.3.weight"] = sd[f"{src}.self_attention.dense.weight"]
        if f"{src}.self_attention.dense.bias" in sd:
            out[f"{dst}.0.3.bias"] = sd[f"{src}.self_attention.dense.bias"]
        out[f"{dst}.1.0.weight"] = \
            sd[f"{src}.post_attention_layernorm.weight"]
        out[f"{dst}.1.0.bias"] = sd[f"{src}.post_attention_layernorm.bias"]
        out[f"{dst}.1.1.weight"] = sd[f"{src}.mlp.dense_h_to_4h.weight"]
        out[f"{dst}.1.3.weight"] = sd[f"{src}.mlp.dense_4h_to_h.weight"]
        if f"{src}.mlp.dense_h_to_4h.bias" in sd:
            out[f"{dst}.1.1.bias"] = sd[f"{src}.mlp.dense_h_to_4h.bias"]
            out[f"{dst}.1.3.bias"] = sd[f"{src}.mlp.dense_4h_to_h.bias"]
    out[f"layers.{1 + n_layer}.weight"] = sd[f"{pfx}.ln_f.weight"]
    out[f"layers.{1 + n_layer}.bias"] = sd[f"{pfx}.ln_f.bias"]
    out[f"layers.{2 + n_layer}.weight"] = sd.get(
        "lm_head.weight", sd[f"{pfx}.word_embeddings.weight"])
    return out


def _map_falcon_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """Falcon HF keys → ours: fused QKV de-fused per architecture, the
    norm layout following the block nesting (parallelresidual for the new
    architecture, the shared-norm Phi nesting for 7B-style), tied head."""
    cfg = _llama_text_config(config) if config is not None else None
    if cfg is not None and getattr(cfg, "alibi", False):
        return _map_falcon_rw_state_dict(
            sd, n_layer, int(cfg.num_attention_heads))
    cfg = _llama_text_config(config)
    new_arch, kv = _falcon_arch(cfg)
    heads = int(cfg.num_attention_heads)
    multi_query = bool(getattr(cfg, "multi_query", True))
    out = {"layers.0.weight": sd["transformer.word_embeddings.weight"]}
    for i in range(n_layer):
        src = f"transformer.h.{i}"
        dst = f"layers.{1 + i}"
        qkv_w = _falcon_defuse_qkv(
            sd[f"{src}.self_attention.query_key_value.weight"], heads, kv,
            new_arch, multi_query)
        qkv_b = None
        if f"{src}.self_attention.query_key_value.bias" in sd:
            qkv_b = _falcon_defuse_qkv(
                sd[f"{src}.self_attention.query_key_value.bias"], heads, kv,
                new_arch, multi_query)
        if new_arch:
            attn, mlp = f"{dst}.0", f"{dst}.1"
            for name in ("weight", "bias"):
                out[f"{attn}.0.{name}"] = sd[f"{src}.ln_attn.{name}"]
                out[f"{mlp}.0.{name}"] = sd[f"{src}.ln_mlp.{name}"]
            qkv_at, dense_at, h4h_at, fh_at = (f"{attn}.1", f"{attn}.3",
                                               f"{mlp}.1", f"{mlp}.3")
        else:
            for name in ("weight", "bias"):
                out[f"{dst}.0.0.{name}"] = \
                    sd[f"{src}.input_layernorm.{name}"]
            qkv_at, dense_at, h4h_at, fh_at = (f"{dst}.0.1.0.0",
                                               f"{dst}.0.1.0.2",
                                               f"{dst}.0.1.1.0",
                                               f"{dst}.0.1.1.2")
        out[f"{qkv_at}.weight"] = qkv_w
        if qkv_b is not None:
            out[f"{qkv_at}.bias"] = qkv_b
        for at, hf in ((dense_at, "self_attention.dense"),
                       (h4h_at, "mlp.dense_h_to_4h"),
                       (fh_at, "mlp.dense_4h_to_h")):
            out[f"{at}.weight"] = sd[f"{src}.{hf}.weight"]
            if f"{src}.{hf}.bias" in sd:
                out[f"{at}.bias"] = sd[f"{src}.{hf}.bias"]
    for name in ("weight", "bias"):
        out[f"layers.{1 + n_layer}.{name}"] = sd[f"transformer.ln_f.{name}"]
    out[f"layers.{2 + n_layer}.weight"] = sd.get(
        "lm_head.weight", sd["transformer.word_embeddings.weight"])
    return out


def _map_gptj_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """GPT-J HF keys → ours: q/k rows de-interleaved into half-split
    rotary layout (see ``_gptj_dsl_from_config``), v untouched, shared
    ``ln_1`` re-keyed under the residual/summation nesting, biased head
    kept."""
    cfg = _llama_text_config(config)
    d = int(cfg.hidden_size if hasattr(cfg, "hidden_size") else cfg.n_embd)
    heads = int(getattr(cfg, "num_attention_heads", None) or cfg.n_head)
    rotary_dim = int(getattr(cfg, "rotary_dim", None) or d // heads)
    base = 1 + (1 if float(getattr(cfg, "embd_pdrop", 0.0) or 0.0) else 0)
    out = {"layers.0.weight": sd["transformer.wte.weight"]}
    for i in range(n_layer):
        src = f"transformer.h.{i}"
        dst = f"layers.{base + i}.0"
        for name in ("weight", "bias"):
            out[f"{dst}.0.{name}"] = sd[f"{src}.ln_1.{name}"]
            out[f"{dst}.1.1.0.{name}"] = sd[f"{src}.mlp.fc_in.{name}"]
            out[f"{dst}.1.1.2.{name}"] = sd[f"{src}.mlp.fc_out.{name}"]
        out[f"{dst}.1.0.0.weight"] = np.concatenate(
            [_gptj_deinterleave(sd[f"{src}.attn.q_proj.weight"], heads,
                                rotary_dim),
             _gptj_deinterleave(sd[f"{src}.attn.k_proj.weight"], heads,
                                rotary_dim),
             np.asarray(sd[f"{src}.attn.v_proj.weight"])], axis=0)
        out[f"{dst}.1.0.2.weight"] = sd[f"{src}.attn.out_proj.weight"]
    for name in ("weight", "bias"):
        out[f"layers.{base + n_layer}.{name}"] = \
            sd[f"transformer.ln_f.{name}"]
        out[f"layers.{base + n_layer + 1}.{name}"] = sd[f"lm_head.{name}"]
    return out


def _map_stablelm_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """StableLM HF keys → ours: the llama mapping verbatim (same block
    key layout) plus the LayerNorm biases llama's RMSNorms don't have."""
    out = _map_llama_state_dict(sd, n_layer, config)
    for i in range(n_layer):
        src = f"model.layers.{i}"
        dst = f"layers.{1 + i}"
        out[f"{dst}.attn_block.0.bias"] = sd[f"{src}.input_layernorm.bias"]
        out[f"{dst}.mlp_block.0.bias"] = \
            sd[f"{src}.post_attention_layernorm.bias"]
    out[f"layers.{1 + n_layer}.bias"] = sd["model.norm.bias"]
    return out


def _map_olmo_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """OLMo v1 HF keys → ours.  The non-parametric LayerNorms carry no
    weights, so only projections and embeddings map; the clamp entry
    shifts the attention branch's item indices when clip_qkv is set."""
    cfg = _llama_text_config(config)
    has_clip = getattr(cfg, "clip_qkv", None) is not None
    # attn branch items: [ln, qkv, (clamp,) attention, o_proj]
    i_attn_out = 4 if has_clip else 3
    out = {"layers.0.weight": sd["model.embed_tokens.weight"]}
    for i in range(n_layer):
        src = f"model.layers.{i}"
        dst = f"layers.{1 + i}"
        _concat_qkv(sd, src, out, f"{dst}.0.1")
        out[f"{dst}.0.{i_attn_out}.weight"] = \
            sd[f"{src}.self_attn.o_proj.weight"]
        if f"{src}.self_attn.o_proj.bias" in sd:
            out[f"{dst}.0.{i_attn_out}.bias"] = \
                sd[f"{src}.self_attn.o_proj.bias"]
        for proj in ("gate_proj", "up_proj", "down_proj"):
            out[f"{dst}.1.1.{proj}.weight"] = sd[f"{src}.mlp.{proj}.weight"]
    out[f"layers.{2 + n_layer}.weight"] = sd.get(
        "lm_head.weight", sd["model.embed_tokens.weight"])
    return out


def _map_olmo2_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """OLMo-2 HF keys → ours: QKV concat, flat q/k-norm weights onto the
    attention module, branch-tail norms from post_attention/
    post_feedforward_layernorm, tied-or-untied lm_head."""
    out = {"layers.0.weight": sd["model.embed_tokens.weight"]}
    for i in range(n_layer):
        src = f"model.layers.{i}"
        dst = f"layers.{1 + i}"
        _concat_qkv(sd, src, out, f"{dst}.0.0")
        out[f"{dst}.0.1.q_norm.weight"] = sd[f"{src}.self_attn.q_norm.weight"]
        out[f"{dst}.0.1.k_norm.weight"] = sd[f"{src}.self_attn.k_norm.weight"]
        out[f"{dst}.0.2.weight"] = sd[f"{src}.self_attn.o_proj.weight"]
        if f"{src}.self_attn.o_proj.bias" in sd:
            out[f"{dst}.0.2.bias"] = sd[f"{src}.self_attn.o_proj.bias"]
        out[f"{dst}.0.3.weight"] = sd[f"{src}.post_attention_layernorm.weight"]
        for proj in ("gate_proj", "up_proj", "down_proj"):
            out[f"{dst}.1.0.{proj}.weight"] = sd[f"{src}.mlp.{proj}.weight"]
        out[f"{dst}.1.1.weight"] = \
            sd[f"{src}.post_feedforward_layernorm.weight"]
    out[f"layers.{1 + n_layer}.weight"] = sd["model.norm.weight"]
    out[f"layers.{2 + n_layer}.weight"] = sd.get(
        "lm_head.weight", sd["model.embed_tokens.weight"])
    return out


def _neox_deinterleave_qkv(w: np.ndarray, heads: int) -> np.ndarray:
    """GPT-NeoX fuses QKV per head ([q_h; k_h; v_h] stacked head-major,
    HF ``modeling_gpt_neox`` view (H, 3, hd, ...)); our attention expects
    [all q; all k; all v].  Works for (3d, d) weights and (3d,) biases."""
    w = np.asarray(w)
    hd3 = w.shape[0] // heads
    return (w.reshape((heads, 3, hd3 // 3) + w.shape[1:])
            .swapaxes(0, 1)
            .reshape((w.shape[0],) + w.shape[1:]))


def _map_neox_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """GPT-NeoX HF keys → ours: per-head-interleaved QKV de-interleaved,
    LayerNorms with biases copied straight, untied ``embed_out`` head."""
    heads = int(getattr(_llama_text_config(config), "num_attention_heads"))
    out = {"layers.0.weight": sd["gpt_neox.embed_in.weight"]}
    for i in range(n_layer):
        src = f"gpt_neox.layers.{i}"
        dst = f"layers.{1 + i}"
        for name in ("weight", "bias"):
            out[f"{dst}.0.0.{name}"] = sd[f"{src}.input_layernorm.{name}"]
            # attention_bias=False checkpoints carry no qkv/dense biases —
            # the DSL builds bias-free linears for them (attn_bias above).
            if f"{src}.attention.query_key_value.{name}" in sd:
                out[f"{dst}.0.1.{name}"] = _neox_deinterleave_qkv(
                    sd[f"{src}.attention.query_key_value.{name}"], heads)
            if f"{src}.attention.dense.{name}" in sd:
                out[f"{dst}.0.3.{name}"] = sd[f"{src}.attention.dense.{name}"]
            out[f"{dst}.1.0.{name}"] = \
                sd[f"{src}.post_attention_layernorm.{name}"]
            out[f"{dst}.1.1.{name}"] = sd[f"{src}.mlp.dense_h_to_4h.{name}"]
            out[f"{dst}.1.3.{name}"] = sd[f"{src}.mlp.dense_4h_to_h.{name}"]
    out[f"layers.{1 + n_layer}.weight"] = sd["gpt_neox.final_layer_norm.weight"]
    out[f"layers.{1 + n_layer}.bias"] = sd["gpt_neox.final_layer_norm.bias"]
    out[f"layers.{2 + n_layer}.weight"] = sd.get(
        "embed_out.weight", sd["gpt_neox.embed_in.weight"])
    return out


def _concat_qkv(sd: dict, src: str, out: dict, dst_key: str,
                q="q_proj", k="k_proj", v="v_proj") -> None:
    """Fuse separate q/k/v projections onto our single QKV linear:
    weights (and biases when present) concatenate on the output dim."""
    attn = f"{src}.self_attn"
    out[f"{dst_key}.weight"] = np.concatenate(
        [np.asarray(sd[f"{attn}.{q}.weight"]),
         np.asarray(sd[f"{attn}.{k}.weight"]),
         np.asarray(sd[f"{attn}.{v}.weight"])], axis=0)
    if f"{attn}.{q}.bias" in sd:
        out[f"{dst_key}.bias"] = np.concatenate(
            [np.asarray(sd[f"{attn}.{q}.bias"]),
             np.asarray(sd[f"{attn}.{k}.bias"]),
             np.asarray(sd[f"{attn}.{v}.bias"])], axis=0)


def _map_llama_state_dict(sd: dict, n_layer: int, config=None) -> dict:
    """Llama/Mistral/Qwen2 HF keys → ours: QKV (+bias) concat, straight
    RMSNorm copy (no Gemma +1 offset), tied-or-untied lm_head."""
    prefix = "model"
    if any(k.startswith("model.language_model.") for k in sd):
        prefix = "model.language_model"
    out = {"layers.0.weight": sd[f"{prefix}.embed_tokens.weight"]}
    for i in range(n_layer):
        src = f"{prefix}.layers.{i}"
        dst = f"layers.{1 + i}"
        out[f"{dst}.attn_block.0.weight"] = sd[f"{src}.input_layernorm.weight"]
        if f"{src}.self_attn.qkv_proj.weight" in sd:
            # Phi-3 stores qkv pre-fused in [q; k; v] order — our layout.
            out[f"{dst}.attn_block.1.weight"] = \
                sd[f"{src}.self_attn.qkv_proj.weight"]
            if f"{src}.self_attn.qkv_proj.bias" in sd:
                out[f"{dst}.attn_block.1.bias"] = \
                    sd[f"{src}.self_attn.qkv_proj.bias"]
        else:
            _concat_qkv(sd, src, out, f"{dst}.attn_block.1")
        out[f"{dst}.attn_block.3.weight"] = sd[f"{src}.self_attn.o_proj.weight"]
        if f"{src}.self_attn.o_proj.bias" in sd:
            out[f"{dst}.attn_block.3.bias"] = sd[f"{src}.self_attn.o_proj.bias"]
        if f"{src}.self_attn.q_norm.weight" in sd:  # qwen3 per-head qk-norm
            out[f"{dst}.attn_block.2.q_norm.weight"] = \
                sd[f"{src}.self_attn.q_norm.weight"]
            out[f"{dst}.attn_block.2.k_norm.weight"] = \
                sd[f"{src}.self_attn.k_norm.weight"]
        out[f"{dst}.mlp_block.0.weight"] = \
            sd[f"{src}.post_attention_layernorm.weight"]
        if f"{src}.mlp.gate_up_proj.weight" in sd:
            # Phi-3 fuses [gate; up] on the output dim; split in half.
            gu = np.asarray(sd[f"{src}.mlp.gate_up_proj.weight"])
            half = gu.shape[0] // 2
            out[f"{dst}.mlp_block.1.gate_proj.weight"] = gu[:half]
            out[f"{dst}.mlp_block.1.up_proj.weight"] = gu[half:]
            out[f"{dst}.mlp_block.1.down_proj.weight"] = \
                sd[f"{src}.mlp.down_proj.weight"]
        elif f"{src}.block_sparse_moe.gate.weight" in sd:
            # Mixtral sparse MoE: per-expert w1/w3/w2 stack onto our
            # leading-E gate/up/down layout; router gate copies straight.
            out[f"{dst}.mlp_block.1.router.weight"] = \
                sd[f"{src}.block_sparse_moe.gate.weight"]
            # Sized from config, not key-probing: a truncated checkpoint
            # missing expert e then fails on its precise absent key
            # instead of a downstream shape mismatch.
            n_exp = int(getattr(_llama_text_config(config),
                                "num_local_experts"))
            for ours, theirs in (("gate_proj", "w1"), ("up_proj", "w3"),
                                 ("down_proj", "w2")):
                out[f"{dst}.mlp_block.1.experts.{ours}.weight"] = np.stack(
                    [np.asarray(sd[f"{src}.block_sparse_moe.experts."
                                   f"{e}.{theirs}.weight"])
                     for e in range(n_exp)])
        elif f"{src}.mlp.gate.weight" in sd:
            # Qwen2-MoE: fine experts + always-on shared expert.
            out[f"{dst}.mlp_block.1.router.weight"] = \
                sd[f"{src}.mlp.gate.weight"]
            n_exp = int(getattr(_llama_text_config(config), "num_experts"))
            for proj in ("gate_proj", "up_proj", "down_proj"):
                out[f"{dst}.mlp_block.1.experts.{proj}.weight"] = np.stack(
                    [np.asarray(sd[f"{src}.mlp.experts.{e}.{proj}.weight"])
                     for e in range(n_exp)])
                out[f"{dst}.mlp_block.1.shared_expert.{proj}.weight"] = \
                    sd[f"{src}.mlp.shared_expert.{proj}.weight"]
            out[f"{dst}.mlp_block.1.shared_expert_gate.weight"] = \
                sd[f"{src}.mlp.shared_expert_gate.weight"]
        else:
            for proj in ("gate_proj", "up_proj", "down_proj"):
                out[f"{dst}.mlp_block.1.{proj}.weight"] = \
                    sd[f"{src}.mlp.{proj}.weight"]
    out[f"layers.{1 + n_layer}.weight"] = sd[f"{prefix}.norm.weight"]
    out[f"layers.{2 + n_layer}.weight"] = sd.get(
        "lm_head.weight", sd[f"{prefix}.embed_tokens.weight"])
    return out
