"""Public wrappers for the int8 quant kernels: arbitrary-rank arrays are
flattened and re-grouped into (n_groups, group) rows so each fp32
scale/zp pair covers ``group`` values regardless of the tensor's last-dim
width (CNN feature maps have as few as 16 channels — per-channel-row
metadata would cost 50% of the wire).

The Pallas pair and the jnp reference are numerically identical. The
kernels run compiled on TPU; on CPU the reference runs, since the
interpreter in the per-step training hot path would be the slowest
option (``repro.kernels.backend``)."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.backend import run_kernel
from repro.kernels.int8_quant.kernel import (int8_dequantize_pallas,
                                             int8_quantize_pallas)
from repro.kernels.int8_quant.ref import (int8_dequantize_ref,
                                          int8_quantize_ref)

GROUP = 256                     # values per scale/zp pair (8 B / 256 B)


def _as_groups(x, group: int):
    flat = x.reshape(-1)
    g = max(1, min(group, flat.size))
    pad = (-flat.size) % g
    if pad:
        # edge-pad: zero-padding would drag the tail group's min/max
        # toward 0 and blow its quantization step ~range/254 bound
        flat = jnp.pad(flat, (0, pad), mode="edge")
    return flat.reshape(-1, g)


def int8_quantize(x, group: int = GROUP):
    """x: any-rank float array -> (q int8 (R,G), scale (R,1), zp (R,1),
    orig_shape). Rows are groups of ``group`` consecutive values (the
    tail group is zero-padded on the wire)."""
    q, scale, zp = _quantize(x, group)
    return q, scale, zp, x.shape


# jitted, so the kernel-or-oracle choice follows the device the caller's
# arrays are placed on, as in every other caller of run_kernel
@functools.partial(jax.jit, static_argnums=1)
def _quantize(x, group):
    return run_kernel(int8_quantize_pallas, _as_groups(x, group),
                      cpu=int8_quantize_ref)


def int8_dequantize(q, scale, zp, shape, dtype=jnp.float32):
    return _dequantize(q, scale, zp, tuple(shape), jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _dequantize(q, scale, zp, shape, dtype):
    x = run_kernel(int8_dequantize_pallas, q, scale, zp, dtype=dtype,
                   cpu=functools.partial(int8_dequantize_ref, dtype=dtype))
    return x.reshape(-1)[:math.prod(shape)].reshape(shape)
