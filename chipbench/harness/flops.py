"""Operations and bytes the algorithms need, from shapes alone.

Matrix products count 2 operations a multiply-add. A training step is
three times its forward pass (the backward pass multiplies twice for
every forward product); recomputed products do not count. Embedding
look-ups, LayerNorm, softmax, GELU and the optimizer are left out: they
are bandwidth, not matrix products. Copied arithmetic: ``bench.py``'s
``6 * (N - N_word_embed) * tokens + 12 * L * B * T^2 * d`` for BERT is
these functions' result written as a constant (PERF.md lists the
original for deletion).
"""

from __future__ import annotations


def transformer_forward_flops_per_token(*, layers, d_model, d_ff, vocab,
                                        context, kv_heads=None, heads=None,
                                        head_extra_dense=False):
    """Forward matrix-product operations for one token that attends to
    ``context`` keys. ``head_extra_dense`` adds BERT's d x d MLM
    transform before the vocabulary projection."""
    kv_share = 1.0 if not heads else (kv_heads or heads) / heads
    proj = 2 * d_model * d_model * (2 + 2 * kv_share)  # q, o, k, v
    ffn = 2 * 2 * d_model * d_ff
    attn = 2 * 2 * context * d_model  # scores and weighted sum
    head = 2 * d_model * vocab + (2 * d_model * d_model
                                  if head_extra_dense else 0)
    return layers * (proj + ffn + attn) + head


def attention_call_cost(*, batch, heads, q_len, k_len, head_dim, itemsize,
                        passes):
    """One flash-attention call. ``passes`` is the number of
    (q_len x k_len x head_dim) matrix products the call has to make:
    2 forward (scores, weighted sum), 3 for the backward's dq half
    (scores again, dP, dq), 4 for its dk/dv half (scores, dv, dP, dk).
    Bytes: q, k, v and the output read or written once (the backward
    halves read dO as well and write their gradients)."""
    flops = passes * 2 * batch * heads * q_len * k_len * head_dim
    tensors = {2: 4, 3: 5, 4: 6}[passes]  # q k v o | q k v do dq | q k v do dk dv
    nbytes = tensors * batch * heads * max(q_len, k_len) * head_dim * itemsize
    return flops, nbytes


def paged_decode_call_cost(*, context_tokens, kv_heads, heads, head_dim,
                           itemsize):
    """One paged-decode call over a batch whose live contexts sum to
    ``context_tokens``: every live K and V row is read once."""
    nbytes = context_tokens * 2 * kv_heads * head_dim * itemsize
    flops = 2 * 2 * context_tokens * heads * head_dim
    return flops, nbytes


def roofline_seconds(flops, nbytes, peaks):
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
