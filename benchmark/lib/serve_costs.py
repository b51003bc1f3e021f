"""Operations a served token needs, from the model's shape: what the whole
serving step is measured against (``metrics/serve_mfu_pct.py``), beside the
kernels' costs in ``lib/kernel_costs.py``.

Counted: what the *algorithm* needs for a dense GPT-2 stack that keeps its
keys and values — a multiply-add as 2 FLOPs, every matmul parameter of the
blocks once per token processed, the attention scores and their product
with the values over the token's own causal context, and the output head
once per token *sampled* (a prompt's tokens but the last need no logits).
Padding of a fused tick, recomputation and the embedding look-ups count
nothing.
"""

from __future__ import annotations


def block_params(d: int, depth: int) -> int:
    """Matmul parameters of the blocks: QKV (3d²), projection (d²) and the
    MLP (8d²) a layer.  Biases, LayerNorms and the embeddings are left out."""
    return depth * 12 * d * d


def attention_flops(context: int, d: int, depth: int) -> float:
    """QK^T and PV of one query over ``context`` keys (itself included), all
    heads, all layers: 4·d FLOPs a key a layer."""
    return 4.0 * depth * d * context


def prefill_flops(prompt: int, d: int, depth: int, vocab: int) -> float:
    """One prompt of ``prompt`` tokens prefilled and its first token
    sampled: the blocks for every token, causal attention (token i attends
    i + 1 keys), the head once."""
    return (2.0 * block_params(d, depth) * prompt
            + attention_flops(prompt * (prompt + 1) // 2, d, depth)
            + 2.0 * d * vocab)


def decode_flops(context: int, d: int, depth: int, vocab: int) -> float:
    """One decode step of a row whose query attends ``context`` keys (the
    token fed in included), and the head for the token it samples."""
    return (2.0 * block_params(d, depth)
            + attention_flops(context, d, depth) + 2.0 * d * vocab)


def window_flops(requests, t0: float, t1: float, d: int, depth: int,
                 vocab: int) -> float:
    """The FLOPs behind every token that reached a client in ``[t0, t1)``,
    counted from the client's side: a request's first token stands for its
    prompt's prefill, its i-th later token for a decode step over
    ``prompt + i`` keys."""
    total = 0.0
    for r in requests:
        p = len(r.prompt)
        for i, t in enumerate(r.token_at):
            if t0 <= t < t1:
                total += (prefill_flops(p, d, depth, vocab) if i == 0
                          else decode_flops(p + i, d, depth, vocab))
    return total
