"""Model FLOPs of a dense GQA decoder (Llama-style: RMSNorm, RoPE, SwiGLU,
tied or untied unembedding), counted from the configuration's sizes.

One token through the model costs 2 FLOPs per weight it multiplies, plus
attention: 2 * H * hd FLOPs for its scores and 2 * H * hd for the weighted
values, per position it attends to, in every layer.  A prefill row computes
logits at its last position only; a decode row at its one position.
Padding rows, padding positions and masked pool rows are not work.
"""
from __future__ import annotations


def matmul_weights_per_layer(c: dict) -> int:
    D, F = c["hidden_size"], c["intermediate_size"]
    H, K, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    return D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F


def token_flops(c: dict, context: int, logits: bool) -> int:
    """FLOPs of one token that attends to ``context`` positions (itself
    included); ``logits`` adds the unembedding."""
    L, H, hd = c["num_hidden_layers"], c["num_attention_heads"], c["head_dim"]
    f = L * (2 * matmul_weights_per_layer(c) + 4 * H * hd * context)
    if logits:
        f += 2 * c["hidden_size"] * c["vocab_size"]
    return f


def prefill_flops(c: dict, tail: int, base: int) -> int:
    """A row prefilling ``tail`` tokens after ``base`` resident ones: the
    token at absolute position p attends to p + 1 positions."""
    L, H, hd = c["num_hidden_layers"], c["num_attention_heads"], c["head_dim"]
    attended = tail * base + tail * (tail + 1) // 2
    per_layer = (2 * matmul_weights_per_layer(c) * tail
                 + 4 * H * hd * attended)
    return L * per_layer + 2 * c["hidden_size"] * c["vocab_size"]


def decode_flops(c: dict, n_valid: int) -> int:
    """A row decoding one token with ``n_valid`` positions in its cache."""
    return token_flops(c, n_valid, logits=True)
