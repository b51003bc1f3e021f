"""Operations and bytes a kernel call needs, from the shapes it really gets.

Kept with the benchmark so that no PR that claims a gain can change what a
roofline share is measured against.  Counted: what the *algorithm* needs —
multiply-adds as 2 FLOPs, each operand read once and each result written
once.  Recomputation inside a kernel (the flash backward recomputes the
probabilities) is not counted as useful work, and a causal mask halves the
score matrix.
"""

from __future__ import annotations


def model_flops_per_token(n_matmul_params: int, depth: int, d_model: int,
                          seq: int) -> float:
    """Forward + backward FLOPs per trained token, nanoGPT/PaLM accounting:
    6 per matmul parameter — embedding look-ups do not count — plus 12·L·d·T
    for the attention scores."""
    return 6.0 * n_matmul_params + 12.0 * depth * d_model * seq


def gpt2_matmul_params(d: int, depth: int, vocab: int) -> int:
    """Parameters that take part in matmuls: per block QKV (3d²), projection
    (d²) and the MLP (8d²), plus the output head (untied in
    ``presets.gpt2_custom``).  Biases, LayerNorms and the two embedding
    tables are left out."""
    return depth * 12 * d * d + d * vocab


def flash_attention(batch: int, heads: int, seq: int, head_dim: int,
                    itemsize: int, causal: bool = True) -> dict:
    """One layer's attention over ``(batch, heads, seq, head_dim)``, forward
    and backward, as the training step calls it.

    Forward: QK^T and PV, 4·T²·D FLOPs per head, halved by the causal mask;
    reads Q, K, V, writes O (the log-sum-exp vector is negligible).
    Backward: dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q — four
    matmuls, 8·T²·D per head, halved; reads Q, K, V, O, dO, writes dQ, dK,
    dV."""
    half = 0.5 if causal else 1.0
    per_head = seq * seq * head_dim * half
    tensor = batch * heads * seq * head_dim * itemsize
    return {"fwd": {"flops": 4.0 * per_head * batch * heads,
                    "bytes": 4.0 * tensor},
            "bwd": {"flops": 8.0 * per_head * batch * heads,
                    "bytes": 8.0 * tensor}}


def ragged_paged_attention(q_tokens: int, kv_tokens_attended: int,
                           kv_tokens_read: int, heads: int, head_dim: int,
                           itemsize: int) -> dict:
    """One layer's ragged paged attention call.  ``q_tokens`` query tokens
    in all; ``kv_tokens_attended`` is the sum over query tokens of the keys
    each one attends (its causal context); ``kv_tokens_read`` the sum over
    rows of the context a row's queries share (each row's pages are read
    once for all of its query tokens).  QK^T and PV: 4·D FLOPs per attended
    (query, key) pair per head; bytes: K and V pages once per row, Q in and
    O out."""
    return {"flops": 4.0 * kv_tokens_attended * head_dim * heads,
            "bytes": (2.0 * kv_tokens_read + 2.0 * q_tokens)
            * heads * head_dim * itemsize}


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for ``cost`` and which bound
    gives it."""
    t_flops = cost["flops"] / peaks["flops_bf16"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
