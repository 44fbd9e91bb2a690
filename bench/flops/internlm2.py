"""Model FLOPs of the InternLM2 decoder (dense, grouped-query attention,
SwiGLU, untied head), from its shapes (a copy of the program's analytic
counts in ``utils/flops``).

A multiply-add is two FLOPs. Training is three times the forward: the
backward pass takes two matmuls for each forward one. Recomputation
(rematerialisation) does not count, and the embedding lookup is free.
"""
from __future__ import annotations


def layer_fwd_flops(c: dict, S: int) -> float:
    """One decoder layer's forward FLOPs for one sequence of S tokens:
    the q, k, v and output projections, causal attention (half of the
    S x S score and value matmuls) and the SwiGLU feed-forward."""
    d, H, K, D, F = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"],
                     c["intermediate_size"])
    proj = 2 * S * d * D * (H + 2 * K) + 2 * S * H * D * d
    attn = 4 * S * S * H * D / 2
    ffn = 6 * S * d * F
    return float(proj + attn + ffn)


def head_fwd_flops(c: dict, S: int) -> float:
    return 2.0 * S * c["hidden_size"] * c["vocab_padded"]


def train_flops_per_token(c: dict, S: int) -> float:
    fwd = c["num_hidden_layers"] * layer_fwd_flops(c, S) + head_fwd_flops(
        c, S)
    return 3.0 * fwd / S
