"""Pure-jnp oracles for the fused cohort-compression kernels. Same math
as kernel.py, no Pallas — the numerics tests assert the Pallas pair
matches these, and the batched comm path runs them on CPU
(``repro.kernels.backend``)."""
from __future__ import annotations

import jax.numpy as jnp

_QMAX = 127.0


def int8_roundtrip_ref(x, dtype=jnp.float32):
    """x: (R, G) float group rows -> dequantize(quantize(x)). Identical
    math to int8_quantize_ref + int8_dequantize_ref composed."""
    x = x.astype(jnp.float32)
    mn = jnp.min(x, axis=1, keepdims=True)
    mx = jnp.max(x, axis=1, keepdims=True)
    scale = jnp.maximum((mx - mn) / (2.0 * _QMAX), 1e-12)
    zp = -_QMAX - mn / scale
    q = jnp.clip(jnp.round(x / scale + zp), -_QMAX, _QMAX)
    return (scale * (q - zp)).astype(dtype)


def sparse_combine_ref(y, mask, scale):
    """(delivered, residual) = (y * mask * scale, y - delivered)."""
    delivered = (y.astype(jnp.float32) * mask
                 * jnp.float32(scale)).astype(y.dtype)
    return delivered, (y - delivered).astype(y.dtype)
