"""Oracles of the held-expert grouped matmuls: ``jax.lax.ragged_dot`` in
the kernels' dtypes (f32 accumulation, the result in ``lhs``'s dtype).
Rows past the groups come out zero here, undefined from the kernel. Like
the kernels, they run once per batch element under ``vmap``
(``ragged_dot`` has no batching rule for a weight shared by the batch)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _ragged(lhs, rhs, group_sizes, *, transpose_rhs: bool):
    if transpose_rhs:
        rhs = rhs.swapaxes(1, 2)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)


def _ragged_t(lhs, rhs, group_sizes):
    shape = (group_sizes.shape[0], lhs.shape[1], rhs.shape[1])
    _, vjp = jax.vjp(
        lambda w: _ragged(lhs, w, group_sizes, transpose_rhs=False),
        jnp.zeros(shape, lhs.dtype))
    return vjp(rhs.astype(lhs.dtype))[0]


def ragged(lhs, rhs, group_sizes, *, transpose_rhs: bool = False):
    """Oracle of ``held_experts_gmm``."""
    return jax.custom_batching.sequential_vmap(functools.partial(
        _ragged, transpose_rhs=transpose_rhs))(lhs, rhs, group_sizes)


def ragged_t(lhs, rhs, group_sizes):
    """Oracle of ``held_experts_tgmm``: per group, lhs[rows]^T @ rhs[rows],
    as the weight gradient of ``ragged``."""
    return jax.custom_batching.sequential_vmap(_ragged_t)(lhs, rhs,
                                                          group_sizes)
