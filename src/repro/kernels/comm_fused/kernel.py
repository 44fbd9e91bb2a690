"""Fused Pallas kernels for the batched cohort-compression hot path.

The sequential comm path runs one jnp dispatch chain per (device,
tensor): quantize kernel -> dequantize kernel (int8), or top-k ->
gather -> scatter (sparsifiers), with the error-feedback residual add /
update as separate elementwise passes around them. These kernels fuse
each roundtrip into a single VMEM pass over a stacked cohort buffer:

``int8_roundtrip_pallas``   (R, G) group rows -> dequantized rows in ONE
                            kernel: row min/max, scale/zp, quantize,
                            dequantize — q/scale/zp never materialize in
                            HBM (the wire bytes they would occupy are
                            priced analytically by the channel).
``sparse_combine_pallas``   given the cohort buffer y = x + r and the
                            survivor mask, emit the delivered tensor
                            ``y * mask * scale`` and the residual dual
                            ``r' = y - delivered`` in one pass (two
                            outputs, one read).

Top-k *selection* itself stays on ``jax.lax.top_k`` (XLA's native
batched operator — sorting networks inside a Pallas TPU kernel are not
supported); everything around it is fused here. The jnp oracles live in
ref.py; ops.py picks kernel vs oracle by the repo-wide rule in
``repro.kernels.backend`` (compiled kernel on TPU, oracle on CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_QMAX = 127.0               # same symmetric affine range as int8_quant


def _int8_roundtrip_kernel(x_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)                  # (BR, G)
    mn = jnp.min(x, axis=1, keepdims=True)
    mx = jnp.max(x, axis=1, keepdims=True)
    scale = jnp.maximum((mx - mn) / (2.0 * _QMAX), 1e-12)
    zp = -_QMAX - mn / scale                            # maps mn -> -127
    q = jnp.clip(jnp.round(x / scale + zp), -_QMAX, _QMAX)
    out_ref[...] = (scale * (q - zp)).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "dtype"))
def int8_roundtrip_pallas(x, *, block_rows: int = 256,
                          dtype=jnp.float32, interpret: bool = True):
    """x: (R, G) float group rows -> dequantize(quantize(x)) of the same
    shape, numerically identical to int8_quantize_pallas followed by
    int8_dequantize_pallas but in one kernel with no intermediate
    q/scale/zp buffers. R need not be a multiple of block_rows."""
    r, g = x.shape
    br = min(block_rows, r)
    nb = pl.cdiv(r, br)
    pad = nb * br - r
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _int8_roundtrip_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((br, g), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, g), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * br, g), dtype),
        interpret=interpret,
    )(x)
    return out[:r]


def _sparse_combine_kernel(y_ref, mask_ref, scale_ref, out_ref, res_ref):
    y = y_ref[...]
    delivered = (y.astype(jnp.float32) * mask_ref[...]
                 * scale_ref[0]).astype(out_ref.dtype)
    out_ref[...] = delivered
    res_ref[...] = (y - delivered).astype(res_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "block_cols", "interpret"))
def sparse_combine_pallas(y, mask, scale, *, block_rows: int = 32,
                          block_cols: int = 4096, interpret: bool = True):
    """y: (D, N) cohort buffer (already residual-added); mask: (D, N)
    0/1 survivor mask; scale: scalar (1.0 for top-k, n/k for unbiased
    rand-k). Returns (delivered, residual) = (y * mask * scale,
    y - delivered) in one fused pass. The grid tiles rows and columns,
    so a block's VMEM footprint is bounded by (block_rows, block_cols)
    whatever N is. D and N need not be multiples of the block: the
    kernel is elementwise, and the out-of-range part of an edge block
    is never stored."""
    d, n = y.shape
    br = min(block_rows, d)
    bc = min(block_cols, n)
    scale = jnp.asarray(scale, jnp.float32).reshape(1)
    tile = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    out, res = pl.pallas_call(
        _sparse_combine_kernel,
        grid=(pl.cdiv(d, br), pl.cdiv(n, bc)),
        in_specs=[tile, tile,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((d, n), y.dtype),
                   jax.ShapeDtypeStruct((d, n), y.dtype)],
        interpret=interpret,
    )(y, mask, scale)
    return out, res
