"""Pure-jnp oracle for the int8 affine quantize/dequantize kernels.
Same math as kernel.py, no Pallas — the numerics tests assert the Pallas
pair matches this reference, and the comm codec runs it on CPU
(``repro.kernels.backend``)."""
from __future__ import annotations

import jax.numpy as jnp

_QMAX = 127.0


def int8_quantize_ref(x):
    """x: (R, C) float -> (q int8, scale f32 (R,1), zp f32 (R,1))."""
    x = x.astype(jnp.float32)
    mn = jnp.min(x, axis=1, keepdims=True)
    mx = jnp.max(x, axis=1, keepdims=True)
    scale = jnp.maximum((mx - mn) / (2.0 * _QMAX), 1e-12)
    zp = -_QMAX - mn / scale
    q = jnp.clip(jnp.round(x / scale + zp), -_QMAX, _QMAX)
    return q.astype(jnp.int8), scale, zp


def int8_dequantize_ref(q, scale, zp, dtype=jnp.float32):
    return (scale * (q.astype(jnp.float32) - zp)).astype(dtype)
