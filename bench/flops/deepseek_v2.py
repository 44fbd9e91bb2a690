"""Model FLOPs of a DeepSeek-V2 decoder held as one chip's expert-parallel
share (latent attention, a leading dense layer, then MoE layers with
shared experts, untied head over the vocabulary slice), from its shapes;
and the operations and HBM bytes of the held experts' grouped-matmul
calls of one step, for their roofline.

A multiply-add is two FLOPs. Training is three times the forward: the
backward pass takes two matmuls for each forward one. Recomputation
(rematerialisation) does not count, the embedding lookup is free, and so
are the router's softmax, top-k, sort and gathers. The held routed
experts count at the rows they are expected to see, T k E_held / E: the
rows actually routed vary with the weights and the data.
"""
from __future__ import annotations


def held_share(c: dict) -> float:
    """E_held / E: the expected share of the k choices held here."""
    return c["n_routed_experts"] / c["published"]["n_routed_experts"]


def mla_fwd_flops(c: dict, S: int) -> float:
    """One latent-attention layer's forward FLOPs for one sequence of S
    tokens: the projections and causal attention (half of the S x S score
    and value matmuls)."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    R, Dn, Dr, Dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    proj = 2 * S * (d * H * (Dn + Dr) + d * R + d * Dr + R * H * (Dn + Dv)
                    + H * Dv * d)
    attn = 2 * S * S * H * (Dn + Dr + Dv) / 2
    return float(proj + attn)


def moe_fwd_flops(c: dict, S: int) -> float:
    """One MoE layer's feed-forward FLOPs for S tokens: the router, the
    held routed experts at their expected rows, the shared experts."""
    d, Fe = c["hidden_size"], c["moe_intermediate_size"]
    E, k = c["published"]["n_routed_experts"], c["num_experts_per_tok"]
    router = 2 * S * d * E
    routed = 6 * S * k * held_share(c) * d * Fe
    shared = 6 * S * d * Fe * c["n_shared_experts"]
    return float(router + routed + shared)


def train_flops_per_token(c: dict, S: int) -> float:
    L, dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    fwd = (L * mla_fwd_flops(c, S)
           + dense * 6 * S * c["hidden_size"] * c["intermediate_size"]
           + (L - dense) * moe_fwd_flops(c, S)
           + 2 * S * c["hidden_size"] * c["vocab_padded"])
    return 3.0 * fwd / S


def gmm_step_calls(c: dict, B: int, S: int, split: int,
                   groups: int) -> list:
    """The held experts' grouped-matmul calls (``held_experts_gmm`` and
    ``held_experts_tgmm``) of one training step of B sequences of S
    tokens, as [(calls, FLOPs of one call, HBM bytes of one call), ...].

    Each MoE layer makes, for each buffer of sorted (token, choice) rows,
    3 forward gmm (gate, up, down), 3 input-gradient gmm and 3 weight-
    gradient tgmm; with ``remat`` the backward pass first runs the 3
    forward gmm again (each block is recomputed whole), so 12. These are
    the kernels' real calls: unlike the model FLOPs above, the roofline
    counts the recomputation. A layer below the split (the client half)
    has one buffer of B S k rows; a server-half layer one per balance
    group, of B S k / groups rows. A call computes the buffer's rows
    routed to the held experts, expected rows E_held / E of them: it
    multiplies each by one expert's d x F weight, or sums their d x F
    products (2 rows d F FLOPs), reads or writes each such row's d and F
    values once, and reads the E_held weight blocks, or writes their
    gradient, once (bf16, 2 bytes)."""
    d, F, k = (c["hidden_size"], c["moe_intermediate_size"],
               c["num_experts_per_tok"])
    held = c["n_routed_experts"]
    per_buffer = 12 if c["remat"] else 9
    out = []
    for layer in range(c["first_k_dense_replace"], c["num_hidden_layers"]):
        n = 1 if layer < split else groups
        rows = B * S * k / n * held_share(c)
        out.append((per_buffer * n, 2.0 * rows * d * F,
                    2.0 * (rows * (d + F) + held * d * F)))
    return out
