"""Operations and bytes of a looped language model's training step and of the
cross-entropy kernels, from the shapes they really get.

The accounting of ``kernel_costs.model_flops_per_token`` (6 per matmul
parameter, 12·d·T for the attention scores of a layer), counted per
*application*: a stack of ``depth`` layers run ``steps`` times with shared
weights does every layer's matmuls and its head's ``steps`` times a token.
Recomputation counts nothing; embedding look-ups, norms and the gate's one
column are left out.
"""

from __future__ import annotations


def matmul_params_per_pass(d: int, heads: int, head_dim: int,
                           intermediate: int, depth: int, vocab: int) -> int:
    """Matmul parameters one pass touches: per layer the fused QKV and the
    output projection (4 · d · heads · head_dim) and the gated MLP's three
    matrices (3 · d · intermediate), plus the untied head (d · vocab)."""
    return depth * (4 * d * heads * head_dim + 3 * d * intermediate) \
        + d * vocab


def flops_per_token(d: int, heads: int, head_dim: int, intermediate: int,
                    depth: int, steps: int, vocab: int, seq: int) -> float:
    """Forward + backward FLOPs per trained token of ``steps`` passes over
    ``depth`` shared layers with an exit (head) after every pass."""
    per_pass = matmul_params_per_pass(d, heads, head_dim, intermediate, depth,
                                      vocab)
    return (6.0 * steps * per_pass
            + 12.0 * (steps * depth) * heads * head_dim * seq)


def cross_entropy(rows: int, vocab: int, itemsize: int) -> dict:
    """The two cross-entropy kernels over ``(rows, vocab)`` logits.  Forward:
    the logits read once (max, sum of exponentials and the label's logit in
    one pass: some 4 operations an element); backward: read once more and
    the gradient written in the logits' type ((softmax − onehot) · scale).
    Per-row vectors are negligible.  Both are bandwidth-bound."""
    logits = float(rows) * vocab * itemsize
    ops = 4.0 * rows * vocab
    return {"fwd": {"flops": ops, "bytes": logits},
            "bwd": {"flops": ops, "bytes": 2.0 * logits}}
