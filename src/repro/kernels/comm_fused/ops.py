"""Public wrappers for the fused cohort-compression kernels.

Input convention: a cohort's cut tensors are stacked into one ``(D, N)``
buffer (one row per device, tensors flattened). Each wrapper runs the
whole codec roundtrip — residual add, select/quantize, decode, residual
update ``r' = (x + r) - decode(encode(x + r))`` — as ONE jitted call per
cohort, donated on accelerator backends so the stacked input buffer is
reused in place (donation is a no-op on CPU, where jax ignores it).

Backend selection is the repo-wide rule (``repro.kernels.backend``):
the Pallas kernels compiled on TPU, the jnp oracles on CPU, for the
sequential and the batched compression paths alike.

Numerics contract (tested): every wrapper is element-for-element the
same math as the sequential per-device codec path in
``repro.comm.codecs`` — the batched channel asserts ≤1e-6 equivalence
on delivered tensors and residuals, and bit-equal wire bytes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.comm_fused.kernel import (int8_roundtrip_pallas,
                                             sparse_combine_pallas)
from repro.kernels.comm_fused.ref import (int8_roundtrip_ref,
                                          sparse_combine_ref)
from repro.kernels.backend import run_kernel
from repro.kernels.int8_quant.ops import GROUP


def _donate(*argnums):
    """Donate the stacked cohort buffers on accelerators; on CPU jax
    ignores donation with a warning per call site, so skip it there."""
    return argnums if jax.default_backend() != "cpu" else ()


def _as_group_rows(x2, group: int):
    """(D, N) -> (D * R, g) group rows, row-major so each device's
    values stay consecutive; per-row edge padding mirrors
    int8_quant.ops._as_groups per device (zero-padding would drag the
    tail group's min/max toward 0)."""
    d, n = x2.shape
    g = max(1, min(group, n))
    pad = (-n) % g
    if pad:
        x2 = jnp.pad(x2, ((0, 0), (0, pad)), mode="edge")
    return x2.reshape(d * ((n + pad) // g), g)


def int8_group_geometry(n: int, group: int = GROUP):
    """(values-per-group g, groups-per-device R) for an N-value device
    row — the shape the wire bytes are metered from (R * g payload
    bytes + R group-metadata records), identical to the sequential
    Int8Codec accounting."""
    g = max(1, min(group, int(n)))
    return g, -(-int(n) // g)


# --------------------------------------------------------------- int8
@functools.lru_cache(maxsize=None)
def _int8_fn(ef: bool, group: int):
    def rt(y):
        d, n = y.shape
        dq = run_kernel(int8_roundtrip_pallas, _as_group_rows(y, group),
                        dtype=y.dtype,
                        cpu=functools.partial(int8_roundtrip_ref,
                                              dtype=y.dtype))
        return dq.reshape(d, -1)[:, :n]

    if ef:
        def fn(x, r):
            y = x + r.astype(x.dtype)
            delivered = rt(y)
            return delivered, y - delivered
        return jax.jit(fn, donate_argnums=_donate(0, 1))

    def fn(x):
        return rt(x), None
    return jax.jit(fn, donate_argnums=_donate(0))


def fused_int8_roundtrip(x, r=None, group: int = GROUP):
    """x: (D, N) stacked cohort; r: matching residual stack or None.
    Returns (delivered, new_residual_or_None), one jitted call."""
    fn = _int8_fn(r is not None, group)
    return fn(x, r) if r is not None else fn(x)


# -------------------------------------------------------- sparsifiers
@functools.lru_cache(maxsize=None)
def _sparse_fn(k: int, ef: bool, has_idx: bool):
    def rt(y, idx, scale):
        d, n = y.shape
        y32 = y.astype(jnp.float32)
        if idx is None:
            # top-k selection rides XLA's native batched operator —
            # row-wise identical to the sequential per-device top_k
            idx = jax.lax.top_k(jnp.abs(y32), k)[1]
        rows = jnp.arange(d)[:, None]
        mask = jnp.zeros((d, n), jnp.float32).at[rows, idx].set(1.0)
        delivered, res = run_kernel(sparse_combine_pallas, y32, mask,
                                    scale, cpu=sparse_combine_ref)
        return delivered.astype(y.dtype), res

    if ef:
        def fn(x, r, *a):
            y = x + r.astype(x.dtype)
            delivered, res = rt(y, a[0] if has_idx else None, a[-1])
            # the fused kernel already emitted the residual dual; it is
            # exact when y is f32 (y32 IS y), recompute otherwise
            new_r = res if y.dtype == jnp.float32 else y - delivered
            return delivered, new_r
        return jax.jit(fn, donate_argnums=_donate(0, 1))

    def fn(x, *a):
        idx = a[0] if has_idx else None
        delivered, _ = rt(x, idx, a[-1])
        return delivered, None
    return jax.jit(fn, donate_argnums=_donate(0))


def fused_sparse_roundtrip(x, r=None, *, k: int, scale=1.0, indices=None):
    """x: (D, N) stacked cohort; keep k entries per row — the k
    largest-magnitude (top-k) when ``indices`` is None, else the given
    (D, k) index rows (rand-k; drawn host-side to preserve the codec's
    per-call counter stream). ``scale`` multiplies survivors (n/k for
    the unbiased rand-k estimator). Returns (delivered,
    new_residual_or_None)."""
    fn = _sparse_fn(int(k), r is not None, indices is not None)
    args = (x,) + ((r,) if r is not None else ())
    if indices is not None:
        args += (jnp.asarray(indices),)
    return fn(*args, jnp.float32(scale))


# --------------------------------------------------------------- cast
@functools.lru_cache(maxsize=None)
def _cast_fn(wire_dtype_name: str, ef: bool):
    wire = jnp.dtype(wire_dtype_name)
    # a downcast roundtrip is a single fused XLA convert pair — no
    # Pallas kernel needed, but it rides the same one-call-per-cohort
    # contract (and the residual update fuses into the same program)

    def rt(y):
        return y.astype(wire).astype(y.dtype)

    if ef:
        def fn(x, r):
            y = x + r.astype(x.dtype)
            delivered = rt(y)
            return delivered, y - delivered
        return jax.jit(fn, donate_argnums=_donate(0, 1))

    def fn(x):
        return rt(x), None
    return jax.jit(fn, donate_argnums=_donate(0))


def fused_cast_roundtrip(x, r=None, *, wire_dtype):
    """bf16/fp16 wire downcast over a stacked (D, N) cohort."""
    fn = _cast_fn(jnp.dtype(wire_dtype).name, r is not None)
    return fn(x, r) if r is not None else fn(x)
