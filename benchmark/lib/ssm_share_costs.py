"""Operations and bytes of a hybrid state-space / sparse-expert model's
training step (one mixer a layer: a Mamba-2 mixer, a LatentMoE or attention),
whole or as one rank's share, and of the layers' kernels, from the shapes and
the rows they really get.

The accounting of ``kernel_costs.model_flops_per_token`` and
``moe_share_costs``: 6 per matmul parameter a token meets, 12 · heads · head
size · keys for an attention layer's scores, and for a mixer's scan three
times its forward products (:func:`scan_forward_flops_per_token`: a backward
is twice a forward, as for a matmul).  A token meets only the routed experts
it was sent to *and that are held*, which is measured (``moe_rows``).
Recomputation counts nothing; embedding look-ups, norms, the convolution's
four taps and the gates are left out.
"""

from __future__ import annotations

from benchmark.lib import kernel_costs


def mixer_params(dims: dict) -> int:
    """A Mamba-2 mixer's two matrices: ``d · (2·d_in + 2·G·N + H)`` and
    ``d_in · d``, ``d_in = H · P``, at the heads and groups held."""
    d_in = dims["m_heads"] * dims["m_head_dim"]
    return (dims["d"] * (2 * d_in + 2 * dims["groups"] * dims["state"]
                         + dims["m_heads"]) + d_in * dims["d"])


def expert_layer_params(dims: dict) -> int:
    """What every token meets of a LatentMoE layer: the router ``d ·
    experts``, both latent projections ``2 · d · latent`` and the shared
    expert's two matrices ``2 · d · shared``."""
    d = dims["d"]
    return d * dims["experts"] + 2 * d * dims["latent"] + 2 * d * dims["shared"]


def attention_params(dims: dict) -> int:
    """The fused projection ``d · (H + 2·KV) · D`` and the output ``H · D ·
    d``."""
    D = dims["head_dim"]
    return (dims["d"] * (dims["heads"] + 2 * dims["kv_heads"]) * D
            + dims["heads"] * D * dims["d"])


def matmul_params_per_token(dims: dict, routed_rows_per_token: float) -> float:
    """Matmul parameters a token meets: each layer's by its kind, the untied
    head ``d · vocab``, and ``2 · latent · moe_intermediate`` a routed row
    (experts that are not gated have two matrices):
    ``routed_rows_per_token`` is (token, choice) pairs sent to held experts,
    summed over the E layers, over tokens."""
    per = {"M": mixer_params(dims), "E": expert_layer_params(dims),
           "*": attention_params(dims)}
    total = float(dims["d"] * dims["vocab"]) + sum(
        per[kind] for kind in dims["pattern"])
    return total + (routed_rows_per_token * 2 * dims["latent"]
                    * dims["moe_intermediate"])


def parameters(dims: dict) -> dict:
    """Every trainable parameter held, a kind of layer at a time (the
    configuration file's table): a layer's matmul parameters above and what
    they leave out (a mixer's convolution taps and bias, ``dt_bias``,
    ``A_log``, D and the gated norm's gain; every held expert whole), each
    layer's norm, the final one, embedding and head.  ``all`` is what
    ``presets.param_count`` must say."""
    d, H = dims["d"], dims["m_heads"]
    d_in = H * dims["m_head_dim"]
    channels = d_in + 2 * dims["groups"] * dims["state"]
    expert = 2 * dims["latent"] * dims["moe_intermediate"]
    per = {"M": mixer_params(dims) + channels * (dims["conv"] + 1) + 3 * H
           + d_in,
           "E": expert_layer_params(dims) + dims["held"] * expert,
           "*": attention_params(dims)}
    ends = 2 * d * dims["vocab"]
    return {**per, "expert": expert, "embedding_and_head": ends,
            "all": sum(per[kind] + d for kind in dims["pattern"]) + ends + d}


def scan_forward_flops_per_token(dims: dict) -> float:
    """What the chunked scan multiplies for one token of one mixer, forward
    (arXiv:2405.21060's four products at chunks of ``L``, the two inside a
    chunk halved by their causal mask): the scores ``C·Bᵀ`` a group ``2 · N
    · L/2``, their product with x a head ``2 · P · L/2``, the chunk's state
    ``2 · P · N`` and the entering state read through C ``2 · P · N`` a
    head."""
    H, G = dims["m_heads"], dims["groups"]
    P, N, L = dims["m_head_dim"], dims["state"], dims["chunk"]
    return float(G * N * L + H * P * L + 4 * H * P * N)


def _scores_and_scans(dims: dict, seq: int) -> tuple[float, float]:
    """(attention scores, scans) forward FLOPs a token over the layers, the
    scores over the causal half."""
    attention = dims["pattern"].count("*") * 2.0 * dims["heads"] \
        * dims["head_dim"] * seq
    scans = dims["pattern"].count("M") * scan_forward_flops_per_token(dims)
    return attention, scans


def forward_flops_per_token(dims: dict, seq: int,
                            routed_rows_per_token: float) -> float:
    """What one token's forward really multiplies: 2 × the matmul parameters
    it meets + the causal half of the attention scores + the scans.  The
    configuration file's "what the cut distorts" reads this."""
    attention, scans = _scores_and_scans(dims, seq)
    return (2.0 * matmul_params_per_token(dims, routed_rows_per_token)
            + attention + scans)


def flops_per_token(dims: dict, seq: int,
                    routed_rows_per_token: float) -> float:
    """Forward + backward FLOPs per trained token by the repo's convention:
    6 × the matmul parameters it meets + 12 · heads · head size · seq an
    attention layer (the whole sequence as keys) + 3 × a mixer's scan."""
    attention, scans = _scores_and_scans(dims, seq)
    return (6.0 * matmul_params_per_token(dims, routed_rows_per_token)
            + 6.0 * attention + 3.0 * scans)


def grouped_products(rows: float, layer_calls: float, held: int, latent: int,
                     width: int, itemsize: int) -> dict:
    """The dropless layers' **two** grouped products (up, down: experts that
    are not gated) in one of their three phases (forward; gradient of the
    rows; gradient of the weights: each is two calls and costs alike) for
    ``rows`` rows really routed, summed over ``layer_calls`` (E layers ×
    micro-steps), at the latent's width.

    FLOPs: ``2 · rows · 2 · latent · width``.  Bytes, every operand read
    once and every result written once: each row once on either side of
    each product (``rows · (2·latent + 2·width)``) and the held experts' two
    stacks once a layer call."""
    return {"flops": 2.0 * rows * 2 * latent * width,
            "bytes": (rows * (2 * latent + 2 * width)
                      + layer_calls * 2.0 * held * latent * width) * itemsize}


def grouped_least_seconds(rows: float, layer_calls: float, dims: dict,
                          peaks: dict, itemsize: int = 2) -> float:
    """The least time the chip could take for all three phases of
    :func:`grouped_products`."""
    cost = grouped_products(rows, layer_calls, dims["held"], dims["latent"],
                            dims["moe_intermediate"], itemsize)
    return 3.0 * kernel_costs.roofline_seconds(cost, peaks)[0]


def scan(dims: dict, tokens: int, itemsize: int = 2) -> dict:
    """One mixer's chunked scan over ``tokens`` tokens, forward and
    backward.  FLOPs: :func:`scan_forward_flops_per_token`, twice that
    backward.  Bytes: x, B, C, Δ read and y written once forward (Δ and the
    boundary states float32); backward reads them and dy and writes the four
    gradients; the chunk-boundary states written forward and read
    backward."""
    H, G = dims["m_heads"], dims["groups"]
    P, N, L = dims["m_head_dim"], dims["state"], dims["chunk"]
    flops = scan_forward_flops_per_token(dims) * tokens
    rows = tokens * (H * P + 2 * G * N) * itemsize + tokens * H * 4
    y = tokens * H * P * itemsize
    boundary = -(-tokens // L) * H * P * N * 4
    return {"fwd": {"flops": flops, "bytes": rows + y + boundary},
            "bwd": {"flops": 2.0 * flops,
                    "bytes": 2.0 * rows + 2.0 * y + boundary}}


def scan_least_seconds(dims: dict, tokens: int, peaks: dict) -> float:
    """The least time the chip could take for one mixer's scan, forward and
    backward (no kernel of the program's is held to it yet: the scan is
    ``jnp`` in chunks, and ``ssd_time_pct`` says what it takes)."""
    cost = scan(dims, tokens)
    return sum(kernel_costs.roofline_seconds(cost[part], peaks)[0]
               for part in ("fwd", "bwd"))
