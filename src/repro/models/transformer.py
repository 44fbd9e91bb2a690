"""Composable decoder assembly.

Blocks are built from the config's (mixer, ffn) pattern; the stack exposes
range-application (``apply_blocks(lo, hi)``) which is what S²FL's sliding
split consumes: the client portion is ``embed + blocks[:s]``, the server
portion is ``blocks[s:] + final_norm + head`` (see repro.core.split).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (cross_entropy, embed, embed_defs, head_defs,
                                 mlp, mlp_defs, rmsnorm, rmsnorm_defs)
from repro.models.params import abstract_params, init_params


# ---------------------------------------------------------------------------
# defs
# ---------------------------------------------------------------------------
def _block_defs(cfg, mixer: str, ffn: str):
    d = cfg.d_model
    defs = {"norm1": rmsnorm_defs(d)}
    if mixer == "ssm":
        defs["mixer"] = ssm_mod.ssm_defs(cfg)
    elif mixer in ("attn", "swa"):
        defs["mixer"] = attn_mod.attn_defs(cfg)
    elif mixer == "shared_attn":
        pass                                   # params live in cfg-level slot
    else:
        raise ValueError(mixer)
    if ffn == "dense":
        defs["norm2"] = rmsnorm_defs(d)
        defs["ffn"] = mlp_defs(d, cfg.d_ff)
    elif ffn == "moe":
        defs["norm2"] = rmsnorm_defs(d)
        defs["ffn"] = moe_mod.moe_defs(cfg)
    return defs


def model_defs(cfg):
    defs = {
        "embed": embed_defs(cfg.vocab_padded, cfg.d_model),
        "blocks": [_block_defs(cfg, m, f) for m, f in cfg.pattern()],
        "final_norm": rmsnorm_defs(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        defs["head"] = head_defs(cfg.d_model, cfg.vocab_padded)
    if any(m == "shared_attn" for m, _ in cfg.pattern()):
        defs["shared_attn"] = {
            "mixer": attn_mod.attn_defs(cfg),
            "norm2": rmsnorm_defs(cfg.d_model),
            "ffn": mlp_defs(cfg.d_model, cfg.d_ff),
        }
    return defs


def init_model(cfg, key):
    return init_params(model_defs(cfg), key, cfg.param_dtype)


def abstract_model(cfg):
    return abstract_params(model_defs(cfg), cfg.param_dtype)


# ---------------------------------------------------------------------------
# forward pieces (split-aware)
# ---------------------------------------------------------------------------
def apply_embed(cfg, params, tokens, prefix_embeds=None):
    """tokens: (B,S) int32; optional prefix_embeds (B,P,d) from a modality
    frontend stub. Returns hidden (B, P+S, d)."""
    h = embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    return h


def _apply_block_kind(cfg, mixer, ffn, bp, shared, h, positions, cache,
                      cache_index):
    """One block of a given (mixer, ffn) kind with explicit params `bp`
    (and the config-level shared-attention params for zamba2-style
    blocks). The indexed and scanned paths both route through here."""
    aux = jnp.zeros((), jnp.float32)

    if mixer == "shared_attn":
        sp = shared
        with jax.named_scope("attention"):
            a, cache = attn_mod.attn_apply(
                cfg, "attn", sp["mixer"],
                rmsnorm(bp["norm1"], h, cfg.norm_eps), positions, cache,
                cache_index)
        h = h + a
        with jax.named_scope("ffn"):
            f = mlp(sp["ffn"], rmsnorm(sp["norm2"], h, cfg.norm_eps),
                    cfg.act)
        return h + f, cache, aux

    if mixer == "ssm":
        a, cache = ssm_mod.ssm_apply(cfg, bp["mixer"],
                                     rmsnorm(bp["norm1"], h, cfg.norm_eps),
                                     cache)
    else:
        with jax.named_scope("attention"):
            a, cache = attn_mod.attn_apply(
                cfg, mixer, bp["mixer"],
                rmsnorm(bp["norm1"], h, cfg.norm_eps), positions, cache,
                cache_index)
    h = h + a

    with jax.named_scope("ffn"):
        if ffn == "dense":
            h = h + mlp(bp["ffn"], rmsnorm(bp["norm2"], h, cfg.norm_eps),
                        cfg.act)
        elif ffn == "moe":
            f, aux = moe_mod.moe_apply(cfg, bp["ffn"],
                                       rmsnorm(bp["norm2"], h, cfg.norm_eps))
            h = h + f
    return h, cache, aux


def _apply_one_block(cfg, params, i, h, positions, cache, cache_index):
    mixer, ffn = cfg.pattern()[i]
    return _apply_block_kind(cfg, mixer, ffn, params["blocks"][i],
                             params.get("shared_attn"), h, positions,
                             cache, cache_index)


def _remat_policy(cfg):
    """'' -> full recompute (minimum memory); 'dots' -> keep matmul
    outputs resident and only recompute elementwise ops (halves the
    re-read bytes of weight-heavy blocks at ~1.5x activation memory —
    the §Perf remat iteration)."""
    if getattr(cfg, "remat_policy", "") == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None


def _segments(cfg, lo: int, hi: int):
    """Maximal runs of identical (mixer, ffn) kind in [lo, hi)."""
    pat = cfg.pattern()
    runs, i = [], lo
    while i < hi:
        j = i
        while j < hi and pat[j] == pat[i]:
            j += 1
        runs.append((i, j, pat[i]))
        i = j
    return runs


_SCAN_MIN_RUN = 3


def _apply_blocks_scanned(cfg, params, h, lo, hi, positions, train):
    """Cacheless path with jax.lax.scan over runs of identical blocks:
    HLO size (and compile time) become O(#distinct block kinds) instead of
    O(n_layers) — essential for the 61-layer MoE / 62-layer dense dry-runs
    on the 512-way mesh (EXPERIMENTS.md §Perf-compile). Per-layer params
    are stacked inside the jitted function, so the param pytree (and its
    shardings) is unchanged at the jit boundary."""
    aux_sum = jnp.zeros((), jnp.float32)
    shared = params.get("shared_attn")
    for (i, j, (mixer, ffn)) in _segments(cfg, lo, hi):
        n = j - i
        if n < _SCAN_MIN_RUN:
            for k in range(i, j):
                h, _, aux = _apply_block_kind(cfg, mixer, ffn,
                                              params["blocks"][k], shared,
                                              h, positions, None, None)
                aux_sum = aux_sum + aux
            continue
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[params["blocks"][k] for k in range(i, j)])

        def body(hh, bp, mixer=mixer, ffn=ffn):
            def blk(hh_, bp_):
                out, _, aux = _apply_block_kind(cfg, mixer, ffn, bp_,
                                                shared, hh_, positions,
                                                None, None)
                return out, aux
            if train and cfg.remat:
                blk = jax.checkpoint(blk, policy=_remat_policy(cfg))
            out, aux = blk(hh, bp)
            return out, aux

        h, auxs = jax.lax.scan(body, h, stacked)
        aux_sum = aux_sum + auxs.sum()
    return h, None, aux_sum


def apply_blocks(cfg, params, h, lo: int, hi: int, positions,
                 caches=None, cache_index=None, train: bool = False):
    """Apply blocks [lo, hi). caches: per-layer list (len n_layers) or None.
    Returns (h, caches, aux_sum). When cfg.scan_layers and no caches are
    involved, identical-block runs are scanned (see _apply_blocks_scanned).
    """
    if caches is None and getattr(cfg, "scan_layers", False):
        return _apply_blocks_scanned(cfg, params, h, lo, hi, positions,
                                     train)
    aux_sum = jnp.zeros((), jnp.float32)
    caches = list(caches) if caches is not None else None
    for i in range(lo, hi):
        c_i = caches[i] if caches is not None else None
        fn = _apply_one_block
        if train and cfg.remat:
            fn = jax.checkpoint(_apply_one_block, static_argnums=(0, 2),
                                policy=_remat_policy(cfg))
        h, c_i, aux = fn(cfg, params, i, h, positions, c_i, cache_index)
        if caches is not None:
            caches[i] = c_i
        aux_sum = aux_sum + aux
    return h, caches, aux_sum


def apply_head(cfg, params, h):
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].astype(h.dtype)
        return h @ w.T
    return h @ params["head"]["w"].astype(h.dtype)


# ---------------------------------------------------------------------------
# whole-model entry points
# ---------------------------------------------------------------------------
def forward(cfg, params, tokens, prefix_embeds=None, train: bool = False):
    """Full forward: logits (B, P+S, vocab_padded), aux loss."""
    h = apply_embed(cfg, params, tokens, prefix_embeds)
    positions = jnp.arange(h.shape[1], dtype=jnp.int32)
    h, _, aux = apply_blocks(cfg, params, h, 0, cfg.n_layers, positions,
                             train=train)
    return apply_head(cfg, params, h), aux


def lm_loss(cfg, params, batch, train: bool = True):
    """batch: {'tokens': (B,S), 'labels': (B,S), optional 'prefix': (B,P,d)}.
    labels[i] is the target for position i (already shifted); -100 ignored."""
    logits, aux = forward(cfg, params, batch["tokens"],
                          batch.get("prefix"), train=train)
    P = logits.shape[1] - batch["tokens"].shape[1]
    if P:
        logits = logits[:, P:]
    ce = cross_entropy(logits, batch["labels"], cfg.vocab_size)
    return ce + aux, {"ce": ce, "aux": aux}


def init_caches(cfg, batch: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    caches = []
    for mixer, _ in cfg.pattern():
        if mixer == "ssm":
            caches.append(ssm_mod.init_ssm_cache(cfg, batch, dtype))
        else:
            caches.append(attn_mod.init_attn_cache(cfg, mixer, batch,
                                                   max_len, dtype))
    return caches


def prefill(cfg, params, tokens, max_len: int, prefix_embeds=None):
    """Run the prompt, build caches. Returns (last_logits, caches, n_prefill)."""
    h = apply_embed(cfg, params, tokens, prefix_embeds)
    S = h.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    caches = init_caches(cfg, tokens.shape[0], max_len)
    h, caches, _ = apply_blocks(cfg, params, h, 0, cfg.n_layers, positions,
                                caches=caches, cache_index=None)
    logits = apply_head(cfg, params, h[:, -1:])
    return logits, caches, S


def decode_step(cfg, params, token, caches, index):
    """One decode step. token: (B,1) int32, index: scalar int32 (current
    position). Returns (logits (B,1,V), caches)."""
    h = apply_embed(cfg, params, token)
    positions = index[None].astype(jnp.int32) if index.ndim == 0 else index
    h, caches, _ = apply_blocks(cfg, params, h, 0, cfg.n_layers, positions,
                                caches=caches, cache_index=index)
    return apply_head(cfg, params, h), caches
