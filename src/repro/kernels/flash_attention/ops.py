"""Jit'd public wrapper: model-layout adapter around the flash kernel.

Compiled on TPU, interpreted on CPU (``repro.kernels.backend``).
"""
from __future__ import annotations

from repro.kernels.backend import run_kernel
from repro.kernels.flash_attention.kernel import flash_attention_fwd


def flash_attention(q, k, v, q_pos=None, k_pos=None, *, window: int = 0,
                    causal: bool = True):
    """Model layout: q (B,S,H,D), k/v (B,T,K,D) -> (B,S,H,Dv).

    Assumes contiguous positions starting at 0 (train/prefill paths).
    """
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    # head order after transpose is (K, G) with G fastest, so the kernel's
    # kv index b // G maps q head (k*G + g) to kv head k.
    qf = q.transpose(0, 2, 1, 3).reshape(B * K * G, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, T, v.shape[-1])
    out = run_kernel(flash_attention_fwd, qf, kf, vf, causal=causal,
                     window=window, groups=G)
    out = out.reshape(B, K, G, S, -1).reshape(B, H, S, -1)
    return out.transpose(0, 2, 1, 3)
