"""``grouped_matmul``: rows sorted by expert times their expert's weights,
differentiable. Forward and input gradient are ``held_experts_gmm``, the
weight gradient ``held_experts_tgmm``: compiled on the TPU, and
``jax.lax.ragged_dot`` as the oracle on the CPU
(``repro.kernels.backend``).

Rows past the groups' end are undefined in the product and in the input
gradient; the caller masks them."""
from __future__ import annotations

import functools

import jax

from repro.kernels.backend import run_kernel
from repro.kernels.moe_gmm.kernel import held_experts_gmm, held_experts_tgmm
from repro.kernels.moe_gmm.ref import ragged, ragged_t


def _gmm(lhs, rhs, group_sizes, transpose_rhs=False):
    return run_kernel(held_experts_gmm, lhs, rhs, group_sizes,
                      cpu=functools.partial(ragged,
                                            transpose_rhs=transpose_rhs),
                      transpose_rhs=transpose_rhs)


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (m, k) sorted by group, rhs (g, k, n), group_sizes (g,) int32
    -> (m, n) in ``lhs``'s dtype."""
    return _gmm(lhs, rhs, group_sizes)


def _fwd(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(res, g):
    lhs, rhs, group_sizes = res
    d_lhs = _gmm(g, rhs, group_sizes, transpose_rhs=True)
    d_rhs = run_kernel(held_experts_tgmm, lhs, g, group_sizes, cpu=ragged_t)
    return d_lhs, d_rhs.astype(rhs.dtype), None


grouped_matmul.defvjp(_fwd, _bwd)
