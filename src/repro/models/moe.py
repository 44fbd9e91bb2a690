"""Mixture-of-Experts FFN for the routed experts one chip holds: top-k
routing over every expert, dropless dispatch to the held experts through
the grouped-matmul kernel, and shared experts.

Expert parallelism divides each layer's routed experts over chips; this
layer is one chip's share. ``cfg.held_experts`` = (first, count) names the
contiguous range of experts held here (empty: all of them, the whole
layer). The router keeps its full width: every token is scored against
every expert, and its top-k choices are made over all of them. The layer
computes only the held experts' part of the result, for the token-expert
pairs routed to them, plus the shared experts, which every chip holds.
What the other chips' experts would add is not computed here, and no code
stands in for them or for the all-to-all that would carry their rows: the
layer's output is this share, and the shares of all chips, with the shared
experts counted once, add up to the whole layer's output.

Dispatch is dropless under any imbalance. The (token, choice) pairs are
sorted by held expert, the pairs of other experts after them; the sorted
buffer has a row for every pair, so no expert can overflow. Only the held
rows are gathered (the rest are filled, not read) and only they are
computed: the kernel's work follows the group sizes. Both the dispatch
gather and the combine gather have gathers as their transposes (each row
is one pair), so neither pass scatters.

The balance loss is DeepSeek's sequence-level one (DeepSeek-V2, arXiv
2405.04434, Sec. 2.2.3): per sequence, alpha * sum_e f_e P_e, with f_e the
expert's share of the sequence's k choices times E and P_e its mean
routing score, averaged over the sequences; it is computed from the full
routing, which each chip has whole.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm import grouped_matmul
from repro.models.layers import mlp
from repro.models.params import ParamDef


def held_range(cfg):
    """(first, count) of the routed experts this layer holds."""
    return tuple(cfg.held_experts) or (0, cfg.n_experts)


def moe_defs(cfg):
    d, F = cfg.d_model, cfg.moe_d_ff
    E = held_range(cfg)[1]
    defs = {
        "router": ParamDef((d, cfg.n_experts), ("embed", "experts"),
                           dtype="float32"),
        "w_gate": ParamDef((E, d, F), ("experts", "embed", "expert_ff")),
        "w_up": ParamDef((E, d, F), ("experts", "embed", "expert_ff")),
        "w_down": ParamDef((E, F, d), ("experts", "expert_ff", "embed")),
    }
    if cfg.n_shared_experts:
        Fs = cfg.moe_d_ff * cfg.n_shared_experts
        defs["shared"] = {
            "w_gate": ParamDef((d, Fs), ("embed", "ff")),
            "w_up": ParamDef((d, Fs), ("embed", "ff")),
            "w_down": ParamDef((Fs, d), ("ff", "embed")),
        }
    return defs


def route(cfg, router, x):
    """x (B, S, d) -> softmax scores (B, S, E) in f32 and the top-k
    (weights, experts), each (B, S, k). The logits are computed in f32 at
    full precision, as the published gate computes them."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(scores, cfg.top_k)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return scores, weights, experts


def sequence_balance_loss(cfg, scores, experts):
    """DeepSeek's sequence-level balance loss from scores (B, S, E) and
    the chosen experts (B, S, k)."""
    _, S, E = scores.shape
    counts = jnp.sum(jax.nn.one_hot(experts, E, dtype=jnp.float32),
                     axis=(1, 2))                            # (B, E)
    f = counts * E / (S * cfg.top_k)
    return cfg.router_aux_coef * jnp.mean(
        jnp.sum(f * jnp.mean(scores, axis=1), -1))


def _take(x, idx):
    """Rows ``x[idx]``; an index past the end gives a zero row, read from
    nowhere."""
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(xt, pair_of_row, row_of_pair, k):
    """The sorted buffer: row i is token ``pair_of_row[i] // k`` (zero past
    the held rows)."""
    return _take(xt, pair_of_row // k)


def _dispatch_fwd(xt, pair_of_row, row_of_pair, k):
    return _dispatch(xt, pair_of_row, row_of_pair, k), (pair_of_row,
                                                        row_of_pair)


def _dispatch_bwd(k, res, g):
    _, row_of_pair = res
    per_pair = _take(g, row_of_pair).astype(jnp.float32)
    dx = per_pair.reshape(-1, k, g.shape[-1]).sum(1).astype(g.dtype)
    return dx, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(y, row_of_pair, pair_of_row):
    """Each pair's row of the sorted buffer, in pair order (zero for the
    pairs of experts held elsewhere)."""
    return _take(y, row_of_pair)


def _unsort_fwd(y, row_of_pair, pair_of_row):
    return _unsort(y, row_of_pair, pair_of_row), pair_of_row


def _unsort_bwd(pair_of_row, g):
    return _take(g, pair_of_row), None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def moe_apply(cfg, p, x):
    """x (B, S, d) -> (this chip's share of the layer's output (B, S, d),
    the balance loss)."""
    B, S, d = x.shape
    T, k = B * S, cfg.top_k
    first, held = held_range(cfg)
    actf = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    xt = x.reshape(T, d)

    with jax.named_scope("route"):
        scores, weights, experts = route(cfg, p["router"], x)
        aux = sequence_balance_loss(cfg, scores, experts)
        local = experts.reshape(-1) - first                   # (T*k,)
        is_held = (local >= 0) & (local < held)
        key = jnp.where(is_held, local, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(
            jnp.int32)
        n_rows = jnp.sum(group_sizes)
        valid = jnp.arange(T * k) < n_rows                    # held rows
        none = T * k                                          # past the end
        pair_of_row = jnp.where(valid, order, none)
        row_of_pair = jnp.full((T * k,), none, jnp.int32).at[order].set(
            jnp.where(valid, jnp.arange(T * k, dtype=jnp.int32), none))
        rows = _dispatch(xt, pair_of_row, row_of_pair, k)

    with jax.named_scope("experts"):
        def gmm(a, name):
            return grouped_matmul(a, p[name].astype(x.dtype), group_sizes)

        h = actf(gmm(rows, "w_gate")) * gmm(rows, "w_up")
        # rows past the held ones are undefined out of the kernel
        h = jnp.where(valid[:, None], h, 0)
        y = gmm(h, "w_down")

    with jax.named_scope("combine"):
        per_pair = _unsort(y, row_of_pair, pair_of_row).reshape(T, k, d)
        out = jnp.sum(per_pair.astype(jnp.float32) *
                      weights.reshape(T, k, 1), axis=1)

    if cfg.n_shared_experts:
        with jax.named_scope("shared_experts"):
            out = out + mlp(p["shared"], xt, cfg.act)
    return out.astype(x.dtype).reshape(B, S, d), aux
