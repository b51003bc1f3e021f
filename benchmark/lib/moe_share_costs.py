"""Operations and bytes of a sparse-expert model's training step, whole or
as one rank's share, and of the dropless layer's grouped products, from the
shapes and the rows they really get.

The accounting of ``kernel_costs.model_flops_per_token``: 6 per matmul
parameter a token meets, 12 · heads · head size · keys a layer for the
attention scores.  What differs from a dense stack: layers differ (head
counts, a window, a dense or a sparse MLP), and a token meets only the
routed experts it was sent to *and that are held*, which is measured
(``moe_rows`` of the program's ``penroz/train_epoch`` counters), not
assumed.  Recomputation counts nothing; embedding look-ups and norms are
left out.
"""

from __future__ import annotations

from benchmark.lib import kernel_costs


def matmul_params_per_token(dims: dict, routed_rows_per_token: float) -> float:
    """Matmul parameters a token meets.  Per layer: the fused projection
    ``d · ((H + 2·KV) · D + H)`` (queries, keys, values, the per-head gate)
    and the output projection ``H · D · d``; a dense MLP's three matrices
    ``3 · d · intermediate``; a sparse block's router ``d · experts`` and
    shared expert ``3 · d · shared``.  Plus the untied head ``d · vocab``,
    plus ``3 · d · moe_intermediate`` a routed row:
    ``routed_rows_per_token`` is (token, choice) pairs sent to held experts,
    summed over the sparse layers, over tokens."""
    d, D, kv = dims["d"], dims["head_dim"], dims["kv_heads"]
    total = float(d * dims["vocab"])
    for heads, mlp in zip(dims["heads"], dims["mlp_types"]):
        total += d * ((heads + 2 * kv) * D + heads) + heads * D * d
        if mlp == "dense":
            total += 3 * d * dims["intermediate"]
        else:
            total += d * dims["experts"] + 3 * d * dims["shared"]
    return total + routed_rows_per_token * 3 * d * dims["moe_intermediate"]


def flops_per_token(dims: dict, seq: int,
                    routed_rows_per_token: float) -> float:
    """Forward + backward FLOPs per trained token: 6 × the matmul
    parameters it meets + 12 · Σ over layers of heads · head size ·
    min(seq, the layer's window)."""
    keys = [min(seq, dims["window"]) if kind == "sliding_attention" else seq
            for kind in dims["layer_types"]]
    scores = sum(h * dims["head_dim"] * k
                 for h, k in zip(dims["heads"], keys))
    return (6.0 * matmul_params_per_token(dims, routed_rows_per_token)
            + 12.0 * scores)


def grouped_products(rows: float, layer_calls: float, held: int, d: int,
                     width: int, itemsize: int) -> dict:
    """The dropless layers' three grouped products (gate, up, down) in one
    of their three phases (forward; gradient of the rows; gradient of the
    weights: each is three calls and costs alike) for ``rows`` rows really
    routed, summed over ``layer_calls`` (sparse layers × micro-steps).

    FLOPs: ``2 · rows · 3 · d · width``.  Bytes, every operand read once and
    every result written once: each row once on either side of each of the
    three products (``rows · (3·d + 3·width)``) and the held experts' three
    stacks once a layer call (read in the first two phases, written in the
    third)."""
    return {"flops": 2.0 * rows * 3 * d * width,
            "bytes": (rows * (3 * d + 3 * width)
                      + layer_calls * 3.0 * held * d * width) * itemsize}


def grouped_least_seconds(rows: float, layer_calls: float, dims: dict,
                          peaks: dict, itemsize: int = 2) -> float:
    """The least time the chip could take for all three phases of
    :func:`grouped_products` (the larger of FLOPs over the peak and bytes
    over the bandwidth, each phase)."""
    cost = grouped_products(rows, layer_calls, dims["held"], dims["d"],
                            dims["moe_intermediate"], itemsize)
    return 3.0 * kernel_costs.roofline_seconds(cost, peaks)[0]
