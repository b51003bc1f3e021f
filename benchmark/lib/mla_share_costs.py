"""Operations and bytes of a latent-attention sparse-expert model's training
step, whole or as one rank's share, and of the flash kernels at unlike score
and value widths, from the shapes and the rows they really get.

The accounting of ``kernel_costs.model_flops_per_token`` and
``moe_share_costs``: 6 per matmul parameter a token meets, and for the
attention scores 6 · heads · (D + Dv) · keys a layer (``12 · d · T`` where
both widths are the model's), keys the full sequence as everywhere in the
repo's convention.  A token meets only the routed experts it was sent to *and
that are held*, which is measured (``moe_rows``).  Recomputation counts
nothing; embedding look-ups, norms, the Sinkhorn iterations and the stream
mixing's multiply-adds (16 · d a sub-block) are left out.
"""

from __future__ import annotations

from benchmark.lib import kernel_costs


def latent_params(dims: dict) -> int:
    """A latent-attention layer's five matrices: ``d · q_rank``, ``q_rank ·
    H · (d_nope + d_rope)``, ``d · (kv_rank + d_rope)``, ``kv_rank · H ·
    (d_nope + d_v)``, ``H · d_v · d``."""
    d, H = dims["d"], dims["heads"]
    return (d * dims["q_rank"] + dims["q_rank"] * H * dims["head_dim"]
            + d * (dims["kv_rank"] + dims["d_rope"])
            + dims["kv_rank"] * H * (dims["d_nope"] + dims["d_v"])
            + H * dims["d_v"] * d)


def mixing_params(dims: dict) -> int:
    """One sub-block's Phi: ``n · d · (2n + n²)``."""
    n = dims["streams"]
    return n * dims["d"] * (2 * n + n * n)


def matmul_params_per_token(dims: dict, routed_rows_per_token: float) -> float:
    """Matmul parameters a token meets.  Per layer: the latent attention's
    five matrices and two sub-blocks' Phi; a dense MLP's three matrices
    ``3 · d · intermediate``; a sparse block's router ``d · experts`` and
    shared expert ``3 · d · shared``.  Plus the untied head ``d · vocab``,
    plus ``3 · d · moe_intermediate`` a routed row: ``routed_rows_per_token``
    is (token, choice) pairs sent to held experts, summed over the sparse
    layers, over tokens."""
    d = dims["d"]
    total = float(d * dims["vocab"])
    for mlp in dims["mlp_types"]:
        total += latent_params(dims) + 2 * mixing_params(dims)
        if mlp == "dense":
            total += 3 * d * dims["intermediate"]
        else:
            total += d * dims["experts"] + 3 * d * dims["shared"]
    return total + routed_rows_per_token * 3 * d * dims["moe_intermediate"]


def parameters(dims: dict) -> int:
    """Every trainable parameter held: the matmul parameters of
    :func:`matmul_params_per_token` with every held expert whole, the
    embedding, the norm gains (two a layer, two a latent attention, the
    final one) and the stream mixing's three scalars and biases."""
    d, n = dims["d"], dims["streams"]
    sparse = sum(kind == "sparse" for kind in dims["mlp_types"])
    depth = len(dims["mlp_types"])
    held = sparse * dims["held"] * 3 * d * dims["moe_intermediate"]
    small = depth * (2 * d + dims["q_rank"] + dims["kv_rank"]
                     + 2 * (3 + 2 * n + n * n)) + d
    return int(matmul_params_per_token(dims, 0.0)) + held + small \
        + d * dims["vocab"]


def forward_flops_per_token(dims: dict, seq: int,
                            routed_rows_per_token: float) -> float:
    """What one token's forward really multiplies: 2 × the matmul parameters
    it meets + the causal half of the scores, ``heads · (D + Dv) · seq`` a
    layer.  The configuration file's "what the cut distorts" reads this."""
    scores = len(dims["mlp_types"]) * dims["heads"] * (
        dims["head_dim"] + dims["d_v"]) * seq
    return 2.0 * matmul_params_per_token(dims, routed_rows_per_token) + scores


def flops_per_token(dims: dict, seq: int,
                    routed_rows_per_token: float) -> float:
    """Forward + backward FLOPs per trained token by the repo's convention:
    6 × the matmul parameters it meets + 6 · Σ over layers of heads ·
    (D + Dv) · seq (the whole sequence as keys)."""
    scores = len(dims["mlp_types"]) * dims["heads"] * (
        dims["head_dim"] + dims["d_v"]) * seq
    return (6.0 * matmul_params_per_token(dims, routed_rows_per_token)
            + 6.0 * scores)


def flash_attention(batch: int, heads: int, seq: int, D: int, Dv: int,
                    itemsize: int) -> dict:
    """One layer's causal attention with q, k ``D`` wide and v, o ``Dv``
    wide, forward and backward, as ``kernel_costs.flash_attention`` counts
    (which this equals at ``D == Dv``).

    Forward: QK^T ``2·D`` and PV ``2·Dv`` a live score; reads q, k, v,
    writes o.  Backward: dP = dO V^T and dV = P^T dO ``2·Dv`` each, dQ =
    dS K and dK = dS^T Q ``2·D`` each, ``2·(2·D + 2·Dv)`` a live score (the
    scores the kernel computes once more are recomputation and count
    nothing, as in ``kernel_costs``); reads q, k, v, o, dO, writes dq, dk,
    dv, each once."""
    live = batch * heads * seq * seq * 0.5
    rows = batch * heads * seq * itemsize
    return {"fwd": {"flops": 2.0 * (D + Dv) * live,
                    "bytes": rows * (2.0 * D + 2.0 * Dv)},
            "bwd": {"flops": 2.0 * (2 * D + 2 * Dv) * live,
                    "bytes": rows * (4.0 * D + 4.0 * Dv)}}


def flash_least_seconds(dims: dict, job: dict, peaks: dict,
                        itemsize: int = 2) -> float:
    """The least time the chip could take for one layer's forward and one
    backward at the job's micro-batch."""
    cost = flash_attention(job["batch_size"], dims["heads"],
                           job["block_size"], dims["head_dim"], dims["d_v"],
                           itemsize)
    return sum(kernel_costs.roofline_seconds(cost[part], peaks)[0]
               for part in ("fwd", "bwd"))
