"""Attention: GQA (full / sliding-window) and MLA (DeepSeek latent), with
train / prefill / decode paths and KV caches.

Memory strategy (TPU-adapted): for long sequences the XLA path uses a
blockwise q-chunk scan (flash-attention schedule expressed in lax.scan with
per-chunk remat) so scores never materialize at (S, S). The Pallas kernel
in ``repro.kernels.flash_attention`` implements the same schedule with
explicit VMEM BlockSpecs for the TPU target; ``cfg.attn_impl`` selects.

Cache layouts (batch-first, sequence second so long-context caches can be
sequence-sharded over the `data` mesh axis):
  full attn : {'k': (B, S, K, D), 'v': (B, S, K, D)}
  swa       : ring buffer {'k': (B, W, K, D), 'v': ..., 'slot_pos': (W,)}
  mla       : {'latent': (B, S, R), 'k_rope': (B, S, Dr)}
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, yarn_mscale
from repro.models.params import ParamDef

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# defs
# ---------------------------------------------------------------------------
def attn_defs(cfg):
    d, H = cfg.d_model, cfg.n_heads
    if cfg.mla:
        R, Dr, Dn, Dv = (cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                         cfg.qk_nope_head_dim, cfg.v_head_dim)
        return {
            "wq": ParamDef((d, H, Dn + Dr), ("embed", "heads", "none")),
            "w_dkv": ParamDef((d, R), ("embed", "lora")),
            "w_kr": ParamDef((d, Dr), ("embed", "none")),
            "latent_norm": ParamDef((R,), ("lora",), init="ones"),
            "w_uk": ParamDef((R, H, Dn), ("lora", "heads", "none")),
            "w_uv": ParamDef((R, H, Dv), ("lora", "heads", "none")),
            "wo": ParamDef((H, Dv, d), ("heads", "none", "embed")),
        }
    K, D = cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": ParamDef((d, H, D), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, K, D), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, K, D), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((H, D, d), ("heads", "head_dim", "embed")),
    }


# ---------------------------------------------------------------------------
# core attention math (grouped, blockwise)
# ---------------------------------------------------------------------------
def _pick_q_block(S: int) -> int:
    for b in (1024, 512, 256, 128):
        if S % b == 0 and S > b:
            return b
    return S


def grouped_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                      causal: bool = True, impl: str = "xla",
                      scale: float | None = None):
    """q: (B,S,H,Dq) k: (B,T,K,Dq) v: (B,T,K,Dv); GQA via H = K*G.

    Returns (B,S,H,Dv). Positions are 1-D int32 arrays (right-aligned,
    no padding semantics — masking is purely positional). ``scale`` of
    the scores: 1/sqrt(Dq) unless given.
    """
    B, S, H, Dq = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(Dq) if scale is None else scale

    if impl == "pallas" and S > 1:
        if scale != 1.0 / math.sqrt(Dq):
            raise NotImplementedError("the kernel scales by 1/sqrt(Dq)")
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, q_pos, k_pos, window=window,
                                      causal=causal)

    qg = q.reshape(B, S, K, G, Dq)

    def block(q_blk, qp_blk):
        s = jnp.einsum("bskgd,btkd->bkgst", q_blk.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        mask = jnp.ones((q_blk.shape[1], T), bool)
        if causal:
            mask &= qp_blk[:, None] >= k_pos[None, :]
        if window:
            mask &= qp_blk[:, None] - k_pos[None, :] < window
        mask &= k_pos[None, :] >= 0
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgst,btkd->bskgd", w.astype(v.dtype), v)
        return o.reshape(B, q_blk.shape[1], H, v.shape[-1])

    qb = _pick_q_block(S)
    if qb == S:
        return block(qg, q_pos)

    n = S // qb
    qg_c = qg.reshape(B, n, qb, K, G, Dq)
    qp_c = q_pos.reshape(n, qb)

    def body(_, inp):
        qi, qpi = inp
        return None, jax.checkpoint(block)(qi, qpi)

    _, out = jax.lax.scan(body, None,
                          (jnp.moveaxis(qg_c, 1, 0), qp_c))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, v.shape[-1])


# ---------------------------------------------------------------------------
# GQA paths
# ---------------------------------------------------------------------------
def init_attn_cache(cfg, kind: str, batch: int, max_len: int, dtype):
    if cfg.mla:
        return {
            "latent": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
        }
    K, D = cfg.n_kv_heads, cfg.head_dim
    L = min(max_len, cfg.sliding_window) if kind == "swa" else max_len
    cache = {"k": jnp.zeros((batch, L, K, D), dtype),
             "v": jnp.zeros((batch, L, K, D), dtype)}
    if kind == "swa":
        cache["slot_pos"] = jnp.full((L,), -1, jnp.int32)
    return cache


def gqa_apply(cfg, kind, p, x, positions, cache=None, cache_index=None):
    """x: (B,S,d). Train: cache None. Prefill: cache dict is filled and
    returned. Decode: S==1, cache_index = current position (scalar)."""
    B, S, d = x.shape
    window = cfg.sliding_window if kind == "swa" else 0
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:                                   # train
        out = grouped_attention(q, k, v, positions, positions,
                                window=window, causal=True,
                                impl=cfg.attn_impl)
    elif S > 1:                                         # prefill
        if window and cache["k"].shape[1] < S:          # fill ring buffer
            # keep the last W positions, laid out so slot == pos % W (the
            # invariant decode appends rely on)
            W = cache["k"].shape[1]
            slots = positions[S - W:] % W
            order = jnp.argsort(slots)
            cache = {"k": k[:, S - W:][:, order], "v": v[:, S - W:][:, order],
                     "slot_pos": positions[S - W:][order]}
        else:
            L = cache["k"].shape[1]
            cache = dict(cache)
            cache["k"] = jax.lax.dynamic_update_slice(
                cache["k"], k, (0, 0, 0, 0))
            cache["v"] = jax.lax.dynamic_update_slice(
                cache["v"], v, (0, 0, 0, 0))
            if "slot_pos" in cache:
                pos_pad = (jnp.pad(positions, (0, L - S), constant_values=-1)
                           if L > S else positions[:L])
                cache["slot_pos"] = pos_pad
        out = grouped_attention(q, k, v, positions, positions,
                                window=window, causal=True,
                                impl=cfg.attn_impl)
    else:                                               # decode, S == 1
        idx = cache_index
        cache = dict(cache)
        if window:
            W = cache["k"].shape[1]
            slot = idx % W
            cache["k"] = jax.lax.dynamic_update_slice(
                cache["k"], k, (0, slot, 0, 0))
            cache["v"] = jax.lax.dynamic_update_slice(
                cache["v"], v, (0, slot, 0, 0))
            cache["slot_pos"] = jax.lax.dynamic_update_slice(
                cache["slot_pos"], idx[None].astype(jnp.int32), (slot,))
            k_pos = cache["slot_pos"]
        else:
            cache["k"] = jax.lax.dynamic_update_slice(
                cache["k"], k, (0, idx, 0, 0))
            cache["v"] = jax.lax.dynamic_update_slice(
                cache["v"], v, (0, idx, 0, 0))
            T = cache["k"].shape[1]
            k_pos = jnp.where(jnp.arange(T) <= idx, jnp.arange(T), -1)
        out = grouped_attention(q, cache["k"], cache["v"], positions, k_pos,
                                window=window, causal=not window, impl="xla")

    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, cache


# ---------------------------------------------------------------------------
# MLA paths
# ---------------------------------------------------------------------------
# The published DeepSeek-V2 checkpoints store each head's rope columns of
# ``wq`` and ``w_kr`` interleaved (pairs (2i, 2i+1)), and their rotary code
# de-interleaves them before rotating the pairs (i, i + Dr/2) as here: a
# fixed permutation of those weight columns, not a different function.
def mla_softmax_scale(cfg) -> float:
    """1/sqrt(qk head dim), times YaRN's mscale(mscale_all_dim)^2 where
    the config scales its rope (DeepSeek-V2's ``softmax_scale``)."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    s = cfg.rope_scaling
    if s is not None and s.mscale_all_dim:
        scale *= yarn_mscale(s.factor, s.mscale_all_dim) ** 2
    return scale


def _mla_latent(cfg, p, x, positions):
    from repro.models.layers import rmsnorm
    latent = x @ p["w_dkv"].astype(x.dtype)
    latent = rmsnorm({"scale": p["latent_norm"]}, latent, cfg.norm_eps)
    k_rope = x @ p["w_kr"].astype(x.dtype)               # (B,S,Dr)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta, cfg.rope_scaling)[:, :, 0, :]
    return latent, k_rope


def mla_apply(cfg, p, x, positions, cache=None, cache_index=None):
    B, S, d = x.shape
    H = cfg.n_heads
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q_nope, q_rope = q[..., :Dn], q[..., Dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    scale = mla_softmax_scale(cfg)

    if cache is None or S > 1:                          # train / prefill
        latent, k_rope = _mla_latent(cfg, p, x, positions)
        k_nope = jnp.einsum("btr,rhk->bthk", latent, p["w_uk"].astype(x.dtype))
        v = jnp.einsum("btr,rhk->bthk", latent, p["w_uv"].astype(x.dtype))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, H, Dr))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = grouped_attention(qq, k, v, positions, positions,
                                causal=True, impl=cfg.attn_impl,
                                scale=scale)
        if cache is not None:
            cache = {
                "latent": jax.lax.dynamic_update_slice(
                    cache["latent"], latent, (0, 0, 0)),
                "k_rope": jax.lax.dynamic_update_slice(
                    cache["k_rope"], k_rope, (0, 0, 0)),
            }
    else:                                               # decode (absorbed)
        idx = cache_index
        latent, k_rope = _mla_latent(cfg, p, x, positions)
        cache = {
            "latent": jax.lax.dynamic_update_slice(
                cache["latent"], latent, (0, idx, 0)),
            "k_rope": jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope, (0, idx, 0)),
        }
        T = cache["latent"].shape[1]
        # absorb w_uk into the query: (B,1,H,R)
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"].astype(x.dtype))
        s = (jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32),
                        cache["latent"].astype(jnp.float32))
             + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                          cache["k_rope"].astype(jnp.float32))) * scale
        valid = jnp.arange(T) <= idx
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", w.astype(x.dtype),
                           cache["latent"])
        out = jnp.einsum("bshr,rhk->bshk", o_lat, p["w_uv"].astype(x.dtype))

    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, cache


def attn_apply(cfg, kind, p, x, positions, cache=None, cache_index=None):
    if cfg.mla:
        return mla_apply(cfg, p, x, positions, cache, cache_index)
    return gqa_apply(cfg, kind, p, x, positions, cache, cache_index)
