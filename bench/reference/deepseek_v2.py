"""Plain float32 reference of a DeepSeek-V2 decoder (arXiv 2405.04434, and
the published DeepSeek-V2-Lite config.json), one chip's expert-parallel
share of it, and one fused S²FL round step over it.

Written from the paper and the published configuration, in straightforward
``jax.numpy``; it imports nothing of the program.

- Multi-head latent attention without query compression: q = x Wq split
  into a no-rope part (``qk_nope_head_dim``) and a rope part
  (``qk_rope_head_dim``); the key-value latent c = RMSNorm(x Wdkv) of
  width ``kv_lora_rank``; per head k = [c Wuk, rope(x Wkr)] (the rope key
  shared by all heads) and v = c Wuv; causal softmax at the scale
  1/sqrt(nope + rope) times YaRN's mscale(mscale_all_dim)^2; output Wo.
- Rotary embedding with YaRN frequencies, as the published
  ``DeepseekV2YarnRotaryEmbedding`` computes them from ``rope_scaling``:
  theta^(-2i/D) blended with itself over ``factor`` by a linear ramp
  between the correction dimensions of ``beta_fast`` and ``beta_slow``
  turns, cos and sin times the ratio of the two mscales.
- Layer 0 a SwiGLU feed-forward of ``intermediate_size``; the others
  mixture-of-experts layers: softmax scores over all ``published``
  routed experts, top-``num_experts_per_tok`` greedy, the weights
  renormalised only if ``norm_topk_prob``, times
  ``routed_scaling_factor``; ``n_shared_experts`` shared experts (one
  SwiGLU of their summed width) on every token.
- The sequence-level balance loss: per sequence, alpha * sum_e f_e P_e
  with f_e = E / (S k) times the sequence's choices of e and P_e the mean
  score of e; the mean over the sequences.
- RMSNorm with ``rms_norm_eps``; an untied head.

The share: the file's ``n_routed_experts`` experts from
``first_held_expert`` are held, and the layer's output is their part of
the result plus the shared experts, as one chip computes it in an
expert-parallel group; the vocabulary is the file's slice. The routed part
is written as every held expert on every token, weighted by its gate where
top-k chose it and by zero elsewhere: no sort, gather or kernel, so it is
independent of the program's dispatch.

The S²FL step follows Algorithm 2 at E = 1 as the fused form computes it
(``reference/internlm2.py``): client half on every sequence, the balance
permutation, each group's server half with its mean token cross-entropy;
the loss is the mean over groups of (cross-entropy + the group's balance
losses) times the clients per group, plus the client half's balance
losses. SGD updates every parameter.

Departures: the published checkpoints store the rope columns of Wq and
Wkr interleaved, which their rotary code de-interleaves; here they are
laid out as the program takes them (pairs (i, i + D/2)), a fixed
permutation of those columns. For memory only: attention is computed one
block of queries at a time, the experts one at a time, and the head with
its cross-entropy one block of rows at a time, each under
``jax.checkpoint``, so that 16,384 tokens fit one chip in float32.

``quant`` rounds every matmul operand, and its gradient in the backward
pass, to a float8 type with a per-tensor scale (None: float32): the
control computes the same step in a lower precision than the program's.
The router's logits are rounded too. ``keep`` below 1 leaves the rest of
each group's tokens out of its loss: a planted fault.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def layout(c: dict) -> dict:
    """Shapes of every parameter, as a pytree of tuples, in the program's
    layout."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    R, Dn, Dr, Dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    E, Eh, Fe = (c["published"]["n_routed_experts"], c["n_routed_experts"],
                 c["moe_intermediate_size"])
    Fs = Fe * c["n_shared_experts"]
    V = c["vocab_padded"]
    mixer = {"wq": (d, H, Dn + Dr), "w_dkv": (d, R), "w_kr": (d, Dr),
             "latent_norm": (R,), "w_uk": (R, H, Dn), "w_uv": (R, H, Dv),
             "wo": (H, Dv, d)}

    def block(i):
        if i < c["first_k_dense_replace"]:
            F = c["intermediate_size"]
            ffn = {"w_gate": (d, F), "w_up": (d, F), "w_down": (F, d)}
        else:
            ffn = {"router": (d, E), "w_gate": (Eh, d, Fe),
                   "w_up": (Eh, d, Fe), "w_down": (Eh, Fe, d),
                   "shared": {"w_gate": (d, Fs), "w_up": (d, Fs),
                              "w_down": (Fs, d)}}
        return {"norm1": {"scale": (d,)}, "mixer": dict(mixer),
                "norm2": {"scale": (d,)}, "ffn": ffn}

    return {"embed": {"tok": (V, d)},
            "blocks": [block(i) for i in range(c["num_hidden_layers"])],
            "final_norm": {"scale": (d,)},
            "head": {"w": (d, V)}}


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _fan_in(path, s) -> int:
    """Inputs summed into each output of a weight: an expert's input
    width, both dims of Wo, else the first dim."""
    name = path[-1].key
    if len(s) == 3 and name in ("w_gate", "w_up", "w_down"):
        return s[1]
    if name == "wo":
        return s[0] * s[1]
    return s[0]


def make_weights(key, c: dict):
    """Seeded float32 weights: norms at one, the embedding N(0, 0.02^2),
    every projection N(0, 1/fan_in). Call under ``jax.jit``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        layout(c), is_leaf=_is_shape)
    out = []
    for i, (path, s) in enumerate(leaves):
        if len(s) == 1:
            out.append(jnp.ones(s, jnp.float32))
            continue
        std = 0.02 if i == 0 else 1.0 / math.sqrt(_fan_in(path, s))
        out.append(std * jax.random.normal(jax.random.fold_in(key, i), s,
                                           jnp.float32))
    return jax.tree.unflatten(treedef, out)


def _round(x, quant):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(
        jnp.finfo(quant).max)
    return (x / scale).astype(quant).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_both(x, quant):
    return _round(x, quant)


def _round_fwd(x, quant):
    return _round(x, quant), None


def _round_bwd(quant, _, g):
    return (_round(g, quant),)


_round_both.defvjp(_round_fwd, _round_bwd)


def _q(x, quant):
    return x if quant is None else _round_both(x, quant)


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, _q(a, quant), _q(b, quant))


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(c: dict):
    """The rope frequencies of the published YaRN rotary embedding."""
    D, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    rs = c["rope_scaling"]
    extra = 1.0 / base ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    inter = extra / rs["factor"]

    def correction_dim(turns):
        return D * math.log(rs["original_max_position_embeddings"] /
                            (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), D - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(D // 2, dtype=jnp.float32) - low) /
                    (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def softmax_scale(c: dict) -> float:
    rs = c["rope_scaling"]
    scale = 1.0 / math.sqrt(c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
    if rs and rs.get("mscale_all_dim"):
        scale *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rope(x, c):
    """x: (B, S, heads, D); position p rotates pair (i, i + D/2) by
    p * inv_freq[i]."""
    S, D = x.shape[1], x.shape[-1]
    rs = c["rope_scaling"]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * yarn_inv_freq(c)[None]
    m = _mscale(rs["factor"], rs["mscale"]) / _mscale(rs["factor"],
                                                      rs["mscale_all_dim"])
    cos = (jnp.cos(ang) * m)[None, :, None, :]
    sin = (jnp.sin(ang) * m)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, p, c, quant, q_block: int = 256):
    B, S, _ = x.shape
    H, Dn = c["num_attention_heads"], c["qk_nope_head_dim"]
    eps = c["rms_norm_eps"]
    q = _mm("bsd,dhk->bshk", x, p["wq"], quant)
    q_nope, q_rope = q[..., :Dn], rope(q[..., Dn:], c)
    latent = rmsnorm(_mm("bsd,dr->bsr", x, p["w_dkv"], quant),
                     p["latent_norm"], eps)
    k_rope = rope(_mm("bsd,dk->bsk", x, p["w_kr"], quant)[:, :, None], c)
    k_nope = _mm("bsr,rhk->bshk", latent, p["w_uk"], quant)
    v = _mm("bsr,rhk->bshk", latent, p["w_uv"], quant)
    scale = softmax_scale(c)
    qb = min(q_block, S)
    n = S // qb

    @jax.checkpoint
    def one_block(args):
        qn, qr, start = args
        s = (_mm("bqhk,bthk->bhqt", qn, k_nope, quant) +
             _mm("bqhk,btk->bhqt", qr, k_rope[:, :, 0], quant)) * scale
        qpos = start + jnp.arange(qb)
        causal = qpos[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return _mm("bhqt,bthk->bqhk", w, v, quant)

    def blocks(t):
        return jnp.moveaxis(t.reshape(B, n, qb, *t.shape[2:]), 1, 0)

    o = jax.lax.map(one_block, (blocks(q_nope), blocks(q_rope),
                                jnp.arange(n) * qb))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, -1)
    return _mm("bshk,hkd->bsd", o, p["wo"], quant)


def swiglu(x, p, quant):
    g = jax.nn.silu(_mm("bsd,df->bsf", x, p["w_gate"], quant))
    u = _mm("bsd,df->bsf", x, p["w_up"], quant)
    return _mm("bsf,fd->bsd", g * u, p["w_down"], quant)


def moe(x, p, c, quant):
    """(held experts' part + shared experts, balance loss, pairs routed to
    held experts)."""
    B, S, _ = x.shape
    E, k = c["published"]["n_routed_experts"], c["num_experts_per_tok"]
    first = c["first_held_expert"]
    scores = jax.nn.softmax(_mm("bsd,de->bse", x, p["router"], quant), -1)
    topw, topi = jax.lax.top_k(scores, k)
    if c["norm_topk_prob"]:
        topw = topw / jnp.sum(topw, -1, keepdims=True)
    topw = topw * c["routed_scaling_factor"]
    counts = jnp.sum(jax.nn.one_hot(topi, E), axis=(1, 2))       # (B, E)
    aux = c["aux_loss_alpha"] * jnp.mean(jnp.sum(
        counts * E / (S * k) * jnp.mean(scores, 1), -1))

    @jax.checkpoint
    def one_expert(out, e):
        w = jnp.sum(jnp.where(topi == first + e, topw, 0.0), -1)  # (B, S)
        pe = {n: jax.lax.dynamic_index_in_dim(p[n], e, keepdims=False)
              for n in ("w_gate", "w_up", "w_down")}
        return out + w[..., None] * swiglu(x, pe, quant), None

    held = p["w_gate"].shape[0]
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(held))
    pairs = jnp.sum((topi >= first) & (topi < first + held))
    return out + swiglu(x, p["shared"], quant), aux, pairs


def layer(x, p, c, quant):
    """(output, balance loss, pairs routed to held experts)."""
    eps = c["rms_norm_eps"]
    x = x + attention(rmsnorm(x, p["norm1"]["scale"], eps), p["mixer"], c,
                      quant)
    h = rmsnorm(x, p["norm2"]["scale"], eps)
    if "router" not in p["ffn"]:
        return x + swiglu(h, p["ffn"], quant), jnp.zeros(()), jnp.zeros(
            (), jnp.int32)
    f, aux, pairs = moe(h, p["ffn"], c, quant)
    return x + f, aux, pairs


def mean_token_ce(h, params, labels, c, quant, rows: int = 1024,
                  keep: float = 1.0):
    """Mean next-token cross-entropy over the vocabulary slice, computed
    ``rows`` tokens at a time, over the first ``keep`` share of the
    tokens."""
    d, vocab = h.shape[-1], c["vocab_size"]
    h = h.reshape(-1, d)
    y = labels.reshape(-1)
    n_keep = int(h.shape[0] * keep)
    h, y = h[:n_keep], y[:n_keep]
    rows = min(rows, n_keep)
    n = h.shape[0] // rows

    @jax.checkpoint
    def block(args):
        hb, yb = args
        hb = rmsnorm(hb, params["final_norm"]["scale"], c["rms_norm_eps"])
        logits = _mm("nd,dv->nv", hb, params["head"]["w"], quant)[:, :vocab]
        picked = jnp.take_along_axis(logits, yb[:, None], 1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)

    sums = jax.lax.map(block, (h.reshape(n, rows, d), y.reshape(n, rows)))
    return jnp.sum(sums) / h.shape[0]


def s2fl_loss(params, batch, c, *, split, n_groups, members, quant=None,
              keep=1.0):
    """(Eq.-3 loss of one fused S²FL step, pairs routed to held
    experts)."""
    lay = jax.checkpoint(lambda x, p: layer(x, p, c, quant))
    h = params["embed"]["tok"][batch["tokens"]]
    aux_client, pairs = 0.0, 0
    for i in range(split):
        h, aux, n = lay(h, params["blocks"][i])
        aux_client, pairs = aux_client + aux, pairs + n
    h = h[batch["perm"]]
    labels = batch["labels"][batch["perm"]]
    B = h.shape[0]
    losses = []
    for g in range(n_groups):
        rows = slice(g * B // n_groups, (g + 1) * B // n_groups)
        hg, aux_server = h[rows], 0.0
        for i in range(split, c["num_hidden_layers"]):
            hg, aux, n = lay(hg, params["blocks"][i])
            aux_server, pairs = aux_server + aux, pairs + n
        losses.append(mean_token_ce(hg, params, labels[rows], c, quant,
                                    keep=keep) + aux_server)
    return jnp.mean(jnp.stack(losses)) * members + aux_client, pairs


def s2fl_step(params, batch, c, *, split, n_groups, members, lr,
              quant=None, keep=1.0, count=False):
    """(loss, new params) of one SGD step on the Eq.-3 loss; with
    ``count``, also the pairs its forward pass routed to held experts."""
    (loss, pairs), g = jax.value_and_grad(s2fl_loss, has_aux=True)(
        params, batch, c, split=split, n_groups=n_groups, members=members,
        quant=quant, keep=keep)
    new = jax.tree.map(lambda w, gw: w - lr * gw, params, g)
    return (loss, new, pairs) if count else (loss, new)
