"""Plain float32 reference of an InternLM2 decoder (arXiv 2403.17297) and
of one fused S²FL round step over it.

Written from the paper and the published configuration, in straightforward
``jax.numpy``: RMSNorm, rotary embeddings (rotate-half form, theta from the
configuration), grouped-query attention with causal softmax, a SwiGLU
feed-forward and an untied output head. It imports nothing of the program.
The S²FL step follows Algorithm 2 at E = 1 as the fused form computes it:
the client half runs layers [0, split), the balance permutation regroups
the cut features, each group runs the server half and takes its mean token
cross-entropy, and the loss is the mean over groups times the clients per
group (Eq. 3 sums the members' losses); SGD updates every parameter.

Two departures, both for memory only: attention is computed one block of
queries at a time, and the head with its cross-entropy one block of rows
at a time, each block under ``jax.checkpoint``, so that a 4,096-token
batch fits one chip in float32.

``quant`` rounds every matmul operand, and its gradient in the backward
pass, to a float8 type with a per-tensor scale (None: float32): the
control computes the same step in a lower precision than the program's.

The parameter pytree has the layout the program takes, so that one set of
weights, made here from the seed, feeds both.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def layout(c: dict) -> dict:
    """Shapes of every parameter, as a pytree of tuples."""
    d, H, K, D, F = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"],
                     c["intermediate_size"])
    V = c["vocab_padded"]
    block = {"norm1": {"scale": (d,)},
             "mixer": {"wq": (d, H, D), "wk": (d, K, D), "wv": (d, K, D),
                       "wo": (H, D, d)},
             "norm2": {"scale": (d,)},
             "ffn": {"w_gate": (d, F), "w_up": (d, F), "w_down": (F, d)}}
    return {"embed": {"tok": (V, d)},
            "blocks": [block for _ in range(c["num_hidden_layers"])],
            "final_norm": {"scale": (d,)},
            "head": {"w": (d, V)}}


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def make_weights(key, c: dict):
    """Seeded float32 weights: norms at one, the embedding N(0, 0.02^2),
    every projection N(0, 1/fan_in). Call under ``jax.jit``."""
    shapes, treedef = jax.tree.flatten(layout(c), is_leaf=_is_shape)
    out = []
    for i, s in enumerate(shapes):
        if len(s) == 1:
            out.append(jnp.ones(s, jnp.float32))
            continue
        std = 0.02 if i == 0 else 1.0 / math.sqrt(s[0])
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


def rope(x, theta):
    """x: (B, S, heads, D); position p rotates pair (i, i + D/2) by
    p * theta^(-2i/D)."""
    S, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, p, c, quant, q_block: int = 512):
    B, S, _ = x.shape
    H, K, D = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    q = rope(_mm("bsd,dhk->bshk", x, p["wq"], quant), c["rope_theta"])
    k = rope(_mm("bsd,dhk->bshk", x, p["wk"], quant), c["rope_theta"])
    v = _mm("bsd,dhk->bshk", x, p["wv"], quant)
    # grouped-query attention: query head h reads key/value head h // (H/K)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    qb = min(q_block, S)
    n = S // qb

    @jax.checkpoint
    def one_block(args):
        qi, start = args
        s = _mm("bqhd,bthd->bhqt", qi, k, quant) / math.sqrt(D)
        qpos = start + jnp.arange(qb)
        causal = qpos[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return _mm("bhqt,bthd->bqhd", w, v, quant)

    qs = jnp.moveaxis(q.reshape(B, n, qb, H, D), 1, 0)
    o = jax.lax.map(one_block, (qs, jnp.arange(n) * qb))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H, D)
    return _mm("bshk,hkd->bsd", o, p["wo"], quant)


def swiglu(x, p, quant):
    g = jax.nn.silu(_mm("bsd,df->bsf", x, p["w_gate"], quant))
    u = _mm("bsd,df->bsf", x, p["w_up"], quant)
    return _mm("bsf,fd->bsd", g * u, p["w_down"], quant)


def layer(x, p, c, quant):
    eps = c["rms_norm_eps"]
    x = x + attention(rmsnorm(x, p["norm1"]["scale"], eps), p["mixer"], c,
                      quant)
    return x + swiglu(rmsnorm(x, p["norm2"]["scale"], eps), p["ffn"], quant)


def mean_token_ce(h, params, labels, c, quant, rows: int = 1024,
                  keep: float = 1.0):
    """Mean next-token cross-entropy over the real vocabulary, computed
    ``rows`` tokens at a time, over the first ``keep`` share of the tokens
    (below 1 only to plant a fault that leaves part of the batch out)."""
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
    """Eq.-3 loss of one fused S²FL step."""
    lay = jax.checkpoint(lambda x, p: layer(x, p, c, quant))
    h = params["embed"]["tok"][batch["tokens"]]
    for i in range(split):
        h = lay(h, params["blocks"][i])
    h = h[batch["perm"]]
    labels = batch["labels"][batch["perm"]]
    B = h.shape[0]
    losses = []
    for g in range(n_groups):
        rows = slice(g * B // n_groups, (g + 1) * B // n_groups)
        hg = h[rows]
        for i in range(split, c["num_hidden_layers"]):
            hg = lay(hg, params["blocks"][i])
        losses.append(mean_token_ce(hg, params, labels[rows], c, quant,
                                    keep=keep))
    return jnp.mean(jnp.stack(losses)) * members


def s2fl_step(params, batch, c, *, split, n_groups, members, lr,
              quant=None, keep=1.0):
    """(loss, new params) of one SGD step on the Eq.-3 loss."""
    loss, g = jax.value_and_grad(s2fl_loss)(
        params, batch, c, split=split, n_groups=n_groups, members=members,
        quant=quant, keep=keep)
    return loss, jax.tree.map(lambda w, gw: w - lr * gw, params, g)
