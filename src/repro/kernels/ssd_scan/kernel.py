"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid: (batch, heads, n_chunks) with the chunk axis innermost-sequential;
blocks are head-major, (chunk, P) per step, so their last two dims meet
the TPU's (8, 128)-or-whole-dimension tiling rule;
the inter-chunk SSM state (P x N, f32) is carried in VMEM scratch across
chunk steps (TPU grid iteration is sequential, so scratch persists).

Per chunk step (l = chunk length):
  1. intra-chunk quadratic term  y_diag = (L ∘ C Bᵀ) (dt ∘ x)
  2. inter-chunk contribution    y_off  = exp(cumsum dA) * (C state)
  3. state update                state  = exp(sum dA) * state + tailᵀ x

VMEM per step: x (l,P) + B,C (l,N) + L (l,l) f32 + state (P,N) f32 —
with l=128, P=64..128, N=64..128 this is < 0.5 MB, comfortably in VMEM;
the MXU sees (l,l)x(l,P) and (l,N)x(N,P) matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dtc_ref, csc_ref, csr_ref, b_ref, c_ref, init_ref,
                y_ref, fs_ref, state_scr, *, l: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = init_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)                  # (l, P)
    dt = dtc_ref[0, 0]                                   # (l, 1)
    cs = csc_ref[0, 0]                                   # (l, 1)
    cs_row = csr_ref[0, 0]                               # (1, l)
    B = b_ref[0].astype(jnp.float32)                     # (l, N)
    C = c_ref[0].astype(jnp.float32)                     # (l, N)

    # intra-chunk: L[i,j] = exp(cs_i - cs_j) for j <= i
    tri = (jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (l, l), 1))
    L = jnp.where(tri, jnp.exp(cs - cs_row), 0.0)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    W = L * scores                                       # (l, l)
    y = jax.lax.dot_general(W, x * dt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y += exp(cs) * (C @ state^T)   state: (P, N)
    state = state_scr[...]
    y_off = jax.lax.dot_general(C, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y + y_off * jnp.exp(cs)).astype(y_ref.dtype)

    # state update: state = exp(sum dA) * state + sum_m tail_m dt_m x_m B_m
    last = cs_row[:, l - 1:]                             # (1, 1)
    tail = jnp.exp(last - cs) * dt                       # (l, 1)
    upd = jax.lax.dot_general(x, B * tail,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_scr[...] = state * jnp.exp(last) + upd

    @pl.when(ci == nc - 1)
    def _done():
        fs_ref[0, 0] = state_scr[...].astype(fs_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x, dt, A, B, C, initial_state=None, *,
                    chunk: int = 128, interpret: bool = True):
    """x: (b,s,h,p), dt: (b,s,h), A: (h,), B/C: (b,s,n).
    Returns (y (b,s,h,p), final_state (b,h,p,n)). s % chunk == 0.

    The kernel runs head-major: x and y move to (b,h,s,p) so each block's
    last two dims are (chunk, p). The per-chunk cumulative decay
    cs = cumsum(dt * A) is formed here and handed over as a column
    (b,h,s,1) and a row (b,h,1,s), with dt as a column, so the kernel
    needs no in-kernel transposes or scans."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), x.dtype)

    dt_h = jnp.swapaxes(dt, 1, 2).astype(jnp.float32)    # (b, h, s)
    dA = dt_h * A.astype(jnp.float32)[None, :, None]
    cs = jnp.cumsum(dA.reshape(b, h, nc, chunk), axis=-1).reshape(b, h, s)

    kernel = functools.partial(_ssd_kernel, l=chunk, nc=nc)
    col = pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0))
    seq = pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0))
    state = pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0))
    y, fs = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            seq, col, col,
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            state,
        ],
        out_specs=[seq, state],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(jnp.swapaxes(x, 1, 2), dt_h[..., None], cs[..., None], cs[:, :, None],
      B, C, initial_state)
    return jnp.swapaxes(y, 1, 2), fs
