"""Ready-made layer-DSL configs for the baseline model families.

The reference embeds exactly one config — the GPT-2-124M `/model/` OpenAPI
example (reference main.py:53-93); these builders generate that same DSL
shape for the whole GPT-2 size ladder (BASELINE.md's gpt2-124M/xl train
configs) plus the makemore-style char-level MLP (BASELINE.md's CPU-parity
config).  All return plain JSON-able DSL lists accepted by ``POST /model/``
and :class:`penroz_tpu.models.dsl.Mapper`.
"""

from __future__ import annotations

GPT2_SIZES = {
    # name: (d_model, heads, depth)
    "gpt2": (768, 12, 12),          # 124M
    "gpt2-medium": (1024, 16, 24),  # 350M
    "gpt2-large": (1280, 20, 36),   # 774M
    "gpt2-xl": (1600, 25, 48),      # 1.5B
}

ADAMW = {"adamw": {"lr": 6e-4, "betas": [0.9, 0.95], "eps": 1e-8}}


def gpt2(size: str = "gpt2", vocab: int = 50304, block: int = 1024,
         dropout: float = 0.0) -> list:
    """GPT-2 style DSL (the reference's /model/ example, main.py:53-84) at
    any ladder size.  ``vocab`` defaults to the 64-padded 50304 the nanoGPT
    lineage uses for MXU-friendly lm-head matmuls."""
    if size not in GPT2_SIZES:
        raise ValueError(f"unknown gpt2 size {size!r}; "
                         f"one of {sorted(GPT2_SIZES)}")
    d, heads, depth = GPT2_SIZES[size]
    return gpt2_custom(d=d, heads=heads, depth=depth, vocab=vocab,
                       block=block, dropout=dropout)


def gpt2_custom(d: int, heads: int, depth: int, vocab: int = 50304,
                block: int = 1024, dropout: float = 0.0) -> list:
    """GPT-2-shaped DSL at arbitrary dimensions — the single source for the
    ladder sizes above, the driver contract's flagship config
    (``__graft_entry__._gpt2_dsl``), ``chip_smoke.py`` and the benchmark's
    configurations (``benchmark/configs/``).
    (The HF-config→DSL builder in models/dsl.py stays separate: it is
    table-driven against the reference's ``mappers.py:121-176`` field
    mapping, which is its own parity contract.)"""
    std = 0.02
    proj_std = std / (2 * depth) ** 0.5
    return ([{"summation": [
                {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
                 "normal": {"mean": 0.0, "std": std}},
                {"position": {"num_embeddings": block, "embedding_dim": d},
                 "normal": {"mean": 0.0, "std": std}}]},
             {"dropout": {"p": dropout}}]
            + [{"residual": [
                {"sequential": [
                    {"layernorm": {"normalized_shape": d}},
                    {"linear": {"in_features": d, "out_features": 3 * d},
                     "normal": {"mean": 0.0, "std": std}, "zeros": {}},
                    {"attention": {"num_heads": heads, "dropout": dropout}},
                    {"linear": {"in_features": d, "out_features": d},
                     "normal": {"mean": 0.0, "std": proj_std}, "zeros": {}},
                    {"dropout": {"p": dropout}}]},
                {"sequential": [
                    {"layernorm": {"normalized_shape": d}},
                    {"linear": {"in_features": d, "out_features": 4 * d},
                     "normal": {"mean": 0.0, "std": std}, "zeros": {}},
                    {"gelu": {"approximate": "tanh"}},
                    {"linear": {"in_features": 4 * d, "out_features": d},
                     "normal": {"mean": 0.0, "std": proj_std}, "zeros": {}},
                    {"dropout": {"p": dropout}}]}]} for _ in range(depth)]
            + [{"layernorm": {"normalized_shape": d}},
               {"linear": {"in_features": d, "out_features": vocab,
                           "bias": False}},
               {"softmaxlast": {"dim": -1}}])


def _ssm_block(d: int, heads: int, head_dim: int, value_dim: int,
               proj_std: float, dropout: float) -> dict:
    """One gated-SSM residual block: LN → fused qkvg projection → O(1)
    recurrent mix → output projection.  The fused linear emits
    ``heads * (2*head_dim + value_dim + 1)`` features — [q | k | v | gate]
    in :class:`penroz_tpu.ops.modules.GatedSSM`'s split order."""
    std = 0.02
    fused = heads * (2 * head_dim + value_dim + 1)
    return {"residual": [
        {"sequential": [
            {"layernorm": {"normalized_shape": d}},
            {"linear": {"in_features": d, "out_features": fused},
             "normal": {"mean": 0.0, "std": std}, "zeros": {}},
            {"ssm": {"num_heads": heads, "head_dim": head_dim,
                     "value_dim": value_dim}},
            {"linear": {"in_features": heads * value_dim, "out_features": d},
             "normal": {"mean": 0.0, "std": proj_std}, "zeros": {}},
            {"dropout": {"p": dropout}}]},
        {"sequential": [
            {"layernorm": {"normalized_shape": d}},
            {"linear": {"in_features": d, "out_features": 4 * d},
             "normal": {"mean": 0.0, "std": std}, "zeros": {}},
            {"gelu": {"approximate": "tanh"}},
            {"linear": {"in_features": 4 * d, "out_features": d},
             "normal": {"mean": 0.0, "std": proj_std}, "zeros": {}},
            {"dropout": {"p": dropout}}]}]}


def hybrid_custom(d: int, heads: int, depth: int, vocab: int = 50304,
                  block: int = 1024, dropout: float = 0.0,
                  ssm_every: int = 2) -> list:
    """Hybrid attention/SSM stack: every ``ssm_every``-th residual block is a
    gated-SSM block (O(1) per-row state), the rest stay full attention
    (O(T) KV rows).  ``ssm_every=1`` yields a pure-SSM model with no KV
    cache at all — both extremes serve through the unified scheduler."""
    base = gpt2_custom(d=d, heads=heads, depth=depth, vocab=vocab,
                       block=block, dropout=dropout)
    proj_std = 0.02 / (2 * depth) ** 0.5
    head_dim = d // heads
    # Blocks occupy base[2:2+depth]; replace the selected ones in place.
    for i in range(depth):
        if i % ssm_every == 0:
            base[2 + i] = _ssm_block(d, heads, head_dim, head_dim,
                                     proj_std, dropout)
    return base


def ouro_custom(d: int, heads: int, head_dim: int, intermediate: int,
                depth: int, steps: int, vocab: int, rope_theta: float = 1e6,
                eps: float = 1e-6, entropy_weight: float = 0.1) -> list:
    """Ouro-shaped looped language model (Zhu et al. 2025, arXiv:2510.25741;
    ``ByteDance/Ouro-*`` ``config.json``) at arbitrary dimensions: ``depth``
    sandwich-norm blocks (RMSNorm before and after each branch; full
    attention with rotate-half RoPE, no bias; SwiGLU) run ``steps`` times
    with shared weights, the final RMSNorm inside the loop, an untied head
    and a one-scalar exit gate after every pass (``ops/modules.py::Looped``).
    Trained with the expected loss over the exit distribution less
    ``entropy_weight`` × its entropy.  N(0, 0.02) initialisation, the
    residual projections scaled by 1/sqrt(2 · depth · steps): each is
    applied ``steps`` times."""
    std = 0.02
    proj_std = std / (2 * depth * steps) ** 0.5
    norm = {"rmsnorm": {"normalized_shape": d, "eps": eps}}

    def linear(fan_in, fan_out, s=std, bias=False):
        entry = {"linear": {"in_features": fan_in, "out_features": fan_out,
                            "bias": bias},
                 "normal": {"mean": 0.0, "std": s}}
        return {**entry, "zeros": {}} if bias else entry

    block = {"transformerblock": {
        "attn_block": {"sequential": [
            norm,
            linear(d, 3 * heads * head_dim),
            {"attention": {"num_heads": heads, "head_dim": head_dim,
                           "rope_theta": rope_theta}},
            linear(heads * head_dim, d, proj_std)]},
        "mlp_block": {"sequential": [
            norm,
            {"gatedmlp": {"in_features": d, "intermediate_size": intermediate,
                          "activation": "silu"}}]},
        "post_attn_norm": norm, "post_mlp_norm": norm,
        "post_norm_on_residual": False}}
    return [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d},
         "normal": {"mean": 0.0, "std": std}},
        {"looped": {"steps": steps, "body": [block for _ in range(depth)],
                    "exit": {"norm": norm, "head": linear(d, vocab),
                             "gate": linear(d, 1, bias=True)},
                    "entropy_weight": entropy_weight}},
        {"softmaxlast": {"dim": -1}}]


def laguna_custom(d: int, head_dim: int, layer_types: list,
                  heads_per_layer: list, kv_heads: int, mlp_layer_types: list,
                  intermediate: int, num_experts: int, top_k: int,
                  moe_intermediate: int, shared_intermediate: int, vocab: int,
                  window: int, rope: dict, experts_held: int | None = None,
                  first_expert: int = 0, routed_scale: float = 1.0,
                  norm_topk: bool = True, eps: float = 1e-6,
                  published_layers: int | None = None) -> list:
    """Laguna-shaped sparse-expert language model (poolside ``Laguna-*``
    ``config.json``) at arbitrary dimensions, whole or as one rank's share
    of an expert- and tensor-parallel layer.

    One pre-norm block a layer (RMSNorm before attention and before the MLP,
    no bias anywhere), a final RMSNorm, an untied head.  Layer ``i``:

    - attention of kind ``layer_types[i]`` with ``heads_per_layer[i]`` query
      heads on ``kv_heads`` K/V heads of size ``head_dim`` and a per-head
      sigmoid gate on its output (``gate: per_head``; the gate's weight is
      the last ``heads`` rows of the fused projection).  ``full_attention``
      and ``sliding_attention`` (a window of ``window`` keys) take their
      rotation from ``rope[kind]``: ``rope_theta``, ``partial_rotary_factor``
      and, for ``rope_type: yarn``, its parameters.
    - ``mlp_layer_types[i]``: ``dense`` is a SwiGLU of ``intermediate``;
      ``sparse`` a router over ``num_experts`` (softmax, ``top_k`` a token,
      renormalised if ``norm_topk``, times ``routed_scale``) over SwiGLU
      experts of ``moe_intermediate`` beside one ungated shared expert of
      ``shared_intermediate``, dispatched dropless.

    The share: ``experts_held`` experts from ``first_expert`` (default all),
    and the caller's ``heads_per_layer`` / ``kv_heads`` / ``vocab`` already
    cut to what this rank holds.  What the absent experts and heads would
    add is left out; nothing stands in for the other ranks.

    N(0, 0.02) initialisation of the linear layers, the attention output
    projections scaled by 1/sqrt(2 · ``published_layers``) (default: the
    layers built)."""
    depth = len(layer_types)
    if not (len(heads_per_layer) == len(mlp_layer_types) == depth):
        raise ValueError("layer_types, heads_per_layer and mlp_layer_types "
                         "name one entry a layer")
    std = 0.02
    proj_std = std / (2 * (published_layers or depth)) ** 0.5
    norm = {"rmsnorm": {"normalized_shape": d, "eps": eps}}

    def linear(fan_in, fan_out, s=std):
        return {"linear": {"in_features": fan_in, "out_features": fan_out,
                           "bias": False},
                "normal": {"mean": 0.0, "std": s}}

    def attention(kind, heads):
        if kind not in ("full_attention", "sliding_attention"):
            raise ValueError(f"unknown layer type {kind!r}")
        spec = rope[kind]
        args = {"num_heads": heads, "num_kv_heads": kv_heads,
                "head_dim": head_dim, "rope_theta": spec["rope_theta"],
                "gate": "per_head"}
        if float(spec.get("partial_rotary_factor", 1)) < 1:
            args["rope_pct"] = spec["partial_rotary_factor"]
        if spec.get("rope_type", "default") != "default":
            args["rope_scaling"] = {
                k: v for k, v in spec.items()
                if k not in ("rope_theta", "partial_rotary_factor")}
        if kind == "sliding_attention":
            args["sliding_window"] = window
        return {"attention": args}

    def mlp(kind):
        if kind == "dense":
            return {"gatedmlp": {"in_features": d,
                                 "intermediate_size": intermediate,
                                 "activation": "silu"}}
        if kind != "sparse":
            raise ValueError(f"unknown MLP layer type {kind!r}")
        return {"moe": {
            "in_features": d, "intermediate_size": moe_intermediate,
            "num_experts": num_experts, "top_k": top_k,
            "activation": "silu", "norm_topk": norm_topk,
            "routed_scale": routed_scale,
            "shared_expert_size": shared_intermediate,
            "shared_expert_gate": False, "dispatch": "dropless",
            "experts_held": experts_held or num_experts,
            "first_expert": first_expert}}

    blocks = []
    for kind, heads, mlp_kind in zip(layer_types, heads_per_layer,
                                     mlp_layer_types):
        fused = (heads + 2 * kv_heads) * head_dim + heads   # [q | k | v | g]
        blocks.append({"transformerblock": {
            "attn_block": {"sequential": [
                norm, linear(d, fused), attention(kind, heads),
                linear(heads * head_dim, d, proj_std)]},
            "mlp_block": {"sequential": [norm, mlp(mlp_kind)]},
            "post_norm_on_residual": False}})
    return ([{"embedding": {"num_embeddings": vocab, "embedding_dim": d},
              "normal": {"mean": 0.0, "std": std}}]
            + blocks
            + [norm, linear(d, vocab), {"softmaxlast": {"dim": -1}}])


def xing_custom(d: int, heads: int, q_rank: int, kv_rank: int, d_nope: int,
                d_rope: int, d_v: int, mlp_layer_types: list,
                intermediate: int, num_experts: int, top_k: int,
                moe_intermediate: int, shared_intermediate: int, vocab: int,
                rope_theta: float = 10000.0, rope_scaling: dict | None = None,
                experts_held: int | None = None, first_expert: int = 0,
                routed_scale: float = 1.0, norm_topk: bool = True,
                streams: int = 4, sinkhorn_iters: int = 20,
                hc_eps: float = 1e-6, res_clamp: list | None = None,
                bias_update_rate: float = 0.001,
                router_bias: list | None = None, eps: float = 1e-6,
                published_layers: int | None = None) -> list:
    """Xing4.0-shaped sparse-expert language model (XingChen-AGI
    ``Xing4.0-*`` ``config.json``) at arbitrary dimensions, whole or as one
    rank's share of an expert-parallel layer.

    The residual path carries ``streams`` streams mixed a token at a time
    (``hyperconnected``: one around each sub-block, the first of the model
    starting every stream as the embedding, the last summing them), a final
    RMSNorm, an untied head.  Layer ``i``:

    - latent attention (``latentattention``): queries through a rank
      ``q_rank`` bottleneck, keys and values from one normed rank
      ``kv_rank`` vector a token, a ``d_rope``-wide rotary key shared by
      ``heads`` heads that are ``d_nope + d_rope`` wide for the scores and
      ``d_v`` for the values; ``rope_scaling`` of type ``yarn`` as the
      module takes it.
    - ``mlp_layer_types[i]``: ``dense`` is a SwiGLU of ``intermediate``;
      ``sparse`` a router over ``num_experts`` (sigmoid scores, ``top_k`` a
      token chosen by score + selection bias, weights renormalised if
      ``norm_topk``, times ``routed_scale``; the bias moves by
      ``bias_update_rate`` an optimizer step) over SwiGLU experts of
      ``moe_intermediate`` beside one ungated shared expert of
      ``shared_intermediate``, dispatched dropless.  ``router_bias``: one
      list of ``num_experts`` values a sparse layer, the selection bias's
      first value (default zeros).

    The share: ``experts_held`` experts from ``first_expert`` (default all)
    and the caller's ``heads`` and ``vocab`` already cut to what this rank
    holds.

    N(0, 0.02) initialisation of the linear layers, the attention output
    projections scaled by 1/sqrt(2 · ``published_layers``) (default: the
    layers built)."""
    depth = len(mlp_layer_types)
    sparse = [i for i, kind in enumerate(mlp_layer_types) if kind == "sparse"]
    if router_bias is not None and len(router_bias) != len(sparse):
        raise ValueError("router_bias names one list a sparse layer")
    std = 0.02
    proj_std = std / (2 * (published_layers or depth)) ** 0.5
    norm = {"rmsnorm": {"normalized_shape": d, "eps": eps}}

    def linear(fan_in, fan_out, s=std):
        return {"linear": {"in_features": fan_in, "out_features": fan_out,
                           "bias": False},
                "normal": {"mean": 0.0, "std": s}}

    attention = {"latentattention": {
        "in_features": d, "num_heads": heads, "q_rank": q_rank,
        "kv_rank": kv_rank, "d_nope": d_nope, "d_rope": d_rope, "d_v": d_v,
        "rope_theta": rope_theta, "eps": eps, "init_std": std,
        "out_init_std": proj_std,
        **({"rope_scaling": rope_scaling} if rope_scaling else {})}}

    def mlp(i):
        kind = mlp_layer_types[i]
        if kind == "dense":
            return {"gatedmlp": {"in_features": d,
                                 "intermediate_size": intermediate,
                                 "activation": "silu"}}
        if kind != "sparse":
            raise ValueError(f"unknown MLP layer type {kind!r}")
        args = {
            "in_features": d, "intermediate_size": moe_intermediate,
            "num_experts": num_experts, "top_k": top_k,
            "activation": "silu", "norm_topk": norm_topk,
            "routed_scale": routed_scale,
            "shared_expert_size": shared_intermediate,
            "shared_expert_gate": False, "dispatch": "dropless",
            "experts_held": experts_held or num_experts,
            "first_expert": first_expert, "scoring": "sigmoid",
            "selection_bias": True, "bias_update_rate": bias_update_rate}
        if router_bias is not None:
            args["selection_bias_init"] = list(router_bias[sparse.index(i)])
        return {"moe": args}

    def mixed(body, **ends):
        return {"hyperconnected": {
            "features": d, "streams": streams,
            "sinkhorn_iters": sinkhorn_iters, "hc_eps": hc_eps,
            "res_clamp": list(res_clamp or (-30.0, 30.0)), "eps": eps,
            **ends,
            "body": {"sequential": [norm, body]}}}

    blocks = [{"sequential": [
        mixed(attention, **({"expand": True} if i == 0 else {})),
        mixed(mlp(i), **({"reduce": True} if i == depth - 1 else {}))]}
        for i in range(depth)]
    return ([{"embedding": {"num_embeddings": vocab, "embedding_dim": d},
              "normal": {"mean": 0.0, "std": std}}]
            + blocks
            + [norm, linear(d, vocab), {"softmaxlast": {"dim": -1}}])


def nemotron_h_custom(d: int, pattern: str, vocab: int, mamba_heads: int,
                      mamba_head_dim: int, n_groups: int, state_size: int,
                      conv_kernel: int, chunk_size: int, heads: int,
                      kv_heads: int, head_dim: int, num_experts: int,
                      top_k: int, moe_intermediate: int, latent: int,
                      shared_intermediate: int, routed_scale: float = 1.0,
                      norm_topk: bool = True,
                      mamba_heads_held: int | None = None,
                      first_mamba_head: int = 0,
                      experts_held: int | None = None, first_expert: int = 0,
                      bias_update_rate: float = 0.001,
                      router_bias: list | None = None, eps: float = 1e-5,
                      dt_min: float = 0.001, dt_max: float = 0.1,
                      dt_floor: float = 1e-4,
                      published_layers: int | None = None) -> list:
    """Nemotron-H-shaped hybrid language model (NVIDIA ``nemotron_h``
    ``config.json``) at arbitrary dimensions, whole or as one rank's share.

    One mixer a layer (``mixerblock``: ``x + mixer(RMSNorm(x))``), a final
    RMSNorm, an untied head.  ``pattern`` is the config's
    ``hybrid_override_pattern``, one letter a layer:

    - ``M`` a Mamba-2 mixer (``mamba2``): ``mamba_heads`` heads of
      ``mamba_head_dim`` in ``n_groups`` groups, state ``state_size``, a
      ``conv_kernel``-tap convolution, chunks of ``chunk_size``.
    - ``E`` a LatentMoE (``moe``): a sigmoid router over ``num_experts``
      with a selection bias, ``top_k`` a token, weights renormalised if
      ``norm_topk`` times ``routed_scale``; experts ``relu(x W1)² W2`` of
      ``moe_intermediate`` in a latent of ``latent`` the layer projects to
      and from; beside one shared expert of ``shared_intermediate`` at the
      full width; dropless.  ``router_bias``: one list of ``num_experts``
      values an ``E`` layer, the selection bias's first value.
    - ``*`` attention: ``heads`` query heads on ``kv_heads`` key/value
      heads of ``head_dim``, causal, no rotary embedding, no bias.

    The share: ``mamba_heads_held`` heads from ``first_mamba_head`` (whole
    groups), ``experts_held`` experts from ``first_expert``, and the
    caller's ``heads``, ``kv_heads`` and ``vocab`` already cut to what this
    rank holds.

    N(0, 0.02) initialisation of the linear layers, every projection onto
    the residual path scaled by 1/sqrt(2 · ``published_layers``) (default:
    the layers built)."""
    unknown = sorted(set(pattern) - set("ME*"))
    if unknown or not pattern:
        raise ValueError(f"hybrid_override_pattern takes M, E and *, one a "
                         f"layer; got {unknown or pattern!r}")
    sparse = [i for i, kind in enumerate(pattern) if kind == "E"]
    if router_bias is not None and len(router_bias) != len(sparse):
        raise ValueError("router_bias names one list an E layer")
    std = 0.02
    proj_std = std / (2 * (published_layers or len(pattern))) ** 0.5
    norm = {"rmsnorm": {"normalized_shape": d, "eps": eps}}

    def linear(fan_in, fan_out, s=std):
        return {"linear": {"in_features": fan_in, "out_features": fan_out,
                           "bias": False},
                "normal": {"mean": 0.0, "std": s}}

    def mixer(i):
        kind = pattern[i]
        if kind == "M":
            return {"mamba2": {
                "in_features": d, "num_heads": mamba_heads,
                "head_dim": mamba_head_dim, "state_size": state_size,
                "n_groups": n_groups, "conv_kernel": conv_kernel,
                "chunk_size": chunk_size,
                "heads_held": mamba_heads_held or mamba_heads,
                "first_head": first_mamba_head, "eps": eps,
                "init_std": std, "out_init_std": proj_std,
                "dt_min": dt_min, "dt_max": dt_max, "dt_floor": dt_floor}}
        if kind == "*":
            return {"sequential": [
                linear(d, (heads + 2 * kv_heads) * head_dim),
                {"attention": {"num_heads": heads, "num_kv_heads": kv_heads,
                               "head_dim": head_dim}},
                linear(heads * head_dim, d, proj_std)]}
        args = {
            "in_features": d, "intermediate_size": moe_intermediate,
            "num_experts": num_experts, "top_k": top_k,
            "activation": "relu2", "latent": latent, "norm_topk": norm_topk,
            "routed_scale": routed_scale,
            "shared_expert_size": shared_intermediate,
            "shared_expert_gate": False, "dispatch": "dropless",
            "experts_held": experts_held or num_experts,
            "first_expert": first_expert, "scoring": "sigmoid",
            "selection_bias": True, "bias_update_rate": bias_update_rate}
        if router_bias is not None:
            args["selection_bias_init"] = list(router_bias[sparse.index(i)])
        return {"moe": args}

    return ([{"embedding": {"num_embeddings": vocab, "embedding_dim": d},
              "normal": {"mean": 0.0, "std": std}}]
            + [{"mixerblock": {"norm": norm, "mixer": mixer(i)}}
               for i in range(len(pattern))]
            + [norm, linear(d, vocab), {"softmaxlast": {"dim": -1}}])


def makemore_mlp(vocab: int = 27, d_embed: int = 10,
                 d_hidden: int = 200) -> list:
    """Char-level MLP in the makemore style (BASELINE.md CPU-parity config):
    per-position embedding → tanh MLP → softmax CE.  Runs the single-process
    CPU path end-to-end (tests/test_model.py::test_mlp_training_per_position
    is the executable spec)."""
    return [
        {"embedding": {"num_embeddings": vocab, "embedding_dim": d_embed},
         "normal": {"mean": 0.0, "std": 0.02}},
        {"linear": {"in_features": d_embed, "out_features": d_hidden},
         "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
        {"tanh": {}},
        {"linear": {"in_features": d_hidden, "out_features": vocab},
         "normal": {"mean": 0.0, "std": 0.02}, "zeros": {}},
        {"softmaxlast": {"dim": -1}},
    ]


def param_count(layers: list, optimizer: dict = ADAMW) -> int:
    """Total parameter count of a DSL config without allocating it:
    ``jax.eval_shape`` traces the initializer to ShapeDtypeStructs, so even
    gpt2-xl counts in milliseconds."""
    import jax
    from penroz_tpu.models.dsl import Mapper
    from penroz_tpu.models.model import CompiledArch
    mapper = Mapper(layers, optimizer)
    arch = CompiledArch.get(mapper.layers)
    import math
    params, _ = jax.eval_shape(lambda: mapper.init_params(arch.mods, seed=0))
    return sum(math.prod(v.shape) for v in params.values())
