"""Basic layers: RMSNorm, MLPs, embeddings, rotary embeddings."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.params import ParamDef


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def rmsnorm_defs(d_model: int):
    return {"scale": ParamDef((d_model,), ("embed",), init="ones")}


def rmsnorm(params, x, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# Gated MLP (swiglu / geglu)
# ---------------------------------------------------------------------------
def mlp_defs(d_model: int, d_ff: int, ff_axis: str = "ff"):
    return {
        "w_gate": ParamDef((d_model, d_ff), ("embed", ff_axis)),
        "w_up": ParamDef((d_model, d_ff), ("embed", ff_axis)),
        "w_down": ParamDef((d_ff, d_model), (ff_axis, "embed")),
    }


def mlp(params, x, act: str = "silu"):
    actf = jax.nn.silu if act == "silu" else jax.nn.gelu
    g = actf(x @ params["w_gate"].astype(x.dtype))
    u = x @ params["w_up"].astype(x.dtype)
    return (g * u) @ params["w_down"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embed_defs(vocab_padded: int, d_model: int):
    return {"tok": ParamDef((vocab_padded, d_model), ("vocab", "embed"),
                            init="normal")}


def embed(params, tokens, compute_dtype):
    return params["tok"].astype(compute_dtype)[tokens]


def unembed(params, h, *, tied: bool, head_params=None):
    w = params["tok"] if tied else head_params["w"]
    if tied:
        return h @ w.astype(h.dtype).T
    return h @ w.astype(h.dtype)


def head_defs(d_model: int, vocab_padded: int):
    return {"w": ParamDef((d_model, vocab_padded), ("embed", "vocab"))}


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (DeepSeek-V2's
    ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_ramp(scaling, head_dim: int, theta: float):
    """YaRN's per-frequency interpolation weight: 0 keeps a pair's
    frequency, 1 divides it by the factor, linear between the pairs that
    turn ``beta_fast`` and ``beta_slow`` times over the original
    context."""
    def dim_of(turns):
        return head_dim * math.log(
            scaling.original_max_position_embeddings /
            (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(scaling.beta_fast)), 0)
    high = min(math.ceil(dim_of(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    return jnp.clip((i - low) / (high - low), 0.0, 1.0)


def rope_freqs(head_dim: int, theta: float, scaling=None):
    """Rotary frequencies; with ``scaling`` (``configs.base.YarnRope``)
    the YaRN frequencies of DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``,
    which blends each frequency with itself over the factor."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if scaling is None:
        return freqs
    ramp = _yarn_ramp(scaling, head_dim, theta)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x, positions, theta: float, scaling=None):
    """x: (..., S, H, D) with positions (..., S) or (S,). The pairs are
    (i, i + D/2). ``scaling``: YaRN (``rope_freqs``), whose cos and sin
    carry the ratio of the two mscales."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)              # (d/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (..., S, d/2)
    # broadcast over head axis: (..., S, 1, d/2)
    ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None:
        m = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def cross_entropy(logits, labels, vocab_size: int, *, mask=None):
    """Mean next-token CE in f32; labels == -100 or mask==0 are ignored.

    logits may be vocab-padded: positions >= vocab_size are masked out.
    """
    logits = logits.astype(jnp.float32)
    if logits.shape[-1] > vocab_size:
        pad = logits.shape[-1] - vocab_size
        neg = jnp.full((pad,), -1e9, logits.dtype)
        logits = logits.at[..., vocab_size:].set(neg)
    valid = (labels >= 0)
    if mask is not None:
        valid = valid & (mask > 0)
    labels_safe = jnp.clip(labels, 0, vocab_size - 1)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels_safe[..., None], axis=-1)[..., 0]
    nll = (logz - ll) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)
