"""The held-expert MoE layer against a dense reference that runs every
held expert on every token and weights it by its gate where top-k chose
it (zero elsewhere): the output, its gradients, the shares of a layer
divided over chips, dropless routing under total imbalance, the
sequence-level balance loss, and the layer under ``vmap``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, make_reduced
from repro.models import moe as moe_mod
from repro.models.layers import mlp
from repro.models.params import init_params

KEY = jax.random.PRNGKey(0)


def _setup(**repl):
    cfg = make_reduced(get_config("deepseek-v2-lite-16b"))
    cfg = dataclasses.replace(cfg, **repl) if repl else cfg
    p = init_params(moe_mod.moe_defs(cfg), KEY, "float32")
    return cfg, p


def _dense(cfg, p, x, first=0):
    """Every held expert on every token, weighted by its gate where top-k
    chose it; plus the shared experts."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    gates = jax.nn.softmax(xt @ p["router"], -1)
    topw, topi = jax.lax.top_k(gates, cfg.top_k)
    if cfg.norm_topk_prob:
        topw = topw / topw.sum(-1, keepdims=True)
    out = jnp.zeros_like(xt)
    for e in range(p["w_gate"].shape[0]):
        w = jnp.sum(jnp.where(topi == first + e, topw, 0.0), -1)
        y = (jax.nn.silu(xt @ p["w_gate"][e]) * (xt @ p["w_up"][e])
             ) @ p["w_down"][e]
        out = out + w[:, None] * y
    if "shared" in p:
        out = out + mlp(p["shared"], xt, cfg.act)
    return out.reshape(B, S, d)


@pytest.mark.parametrize("norm", [False, True])
def test_dispatch_matches_dense_reference(norm):
    """The gate weights are renormalised only where the config says so
    (DeepSeek-V2-Lite does not)."""
    cfg, p = _setup(norm_topk_prob=norm)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model)) * 0.5
    out, _ = moe_mod.moe_apply(cfg, p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_dense(cfg, p, x)),
                               atol=5e-4)


def test_gradients_match_dense_reference():
    cfg, p = _setup()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model)) * 0.5
    r = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def loss(f):
        return lambda p, x: jnp.sum(f(p, x) * r)

    g = jax.grad(loss(lambda p, x: moe_mod.moe_apply(cfg, p, x)[0]),
                 (0, 1))(p, x)
    g_ref = jax.grad(loss(lambda p, x: _dense(cfg, p, x)), (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4 * max(scale, 1.0))


def test_shares_add_up_to_the_whole_layer():
    """A layer of 16 experts divided over 8 chips, 2 held on each: the 8
    shares, with the shared experts (which every chip computes) counted
    once, add up to the uncut layer."""
    cfg, p = _setup(n_experts=16, top_k=4)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg.d_model)) * 0.5
    whole, _ = moe_mod.moe_apply(cfg, p, x)
    shared = mlp(p["shared"], x.reshape(-1, cfg.d_model), cfg.act).reshape(
        x.shape)
    total = -7 * shared
    for c in range(8):
        cfg_c = dataclasses.replace(cfg, held_experts=(2 * c, 2))
        p_c = {k: (v[2 * c:2 * c + 2] if k.startswith("w_") else v)
               for k, v in p.items()}
        out, _ = moe_mod.moe_apply(cfg_c, p_c, x)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_dense(cfg_c, p_c, x, 2 * c)),
                                   atol=5e-4)
        total = total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(whole),
                               np.asarray(_dense(cfg, p, x)), atol=5e-4)


def test_dropless_when_every_token_routes_to_one_held_expert():
    """A router that sends every token first to one held expert: all of
    them are computed there, none is dropped."""
    cfg, p = _setup(n_experts=16, top_k=2)
    cfg = dataclasses.replace(cfg, held_experts=(4, 4))
    p = {k: (v[4:8] if k.startswith("w_") else v) for k, v in p.items()}
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5),
                                  (2, 64, cfg.d_model))) + 0.1
    p["router"] = p["router"].at[:, 5].set(1.0)
    _, _, experts = moe_mod.route(cfg, p["router"], x)
    assert bool(jnp.all(experts[..., 0] == 5))
    out, _ = moe_mod.moe_apply(cfg, p, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense(cfg, p, x, 4)), atol=5e-4)


def test_sequence_balance_loss():
    """alpha * mean over sequences of sum_e f_e P_e, with f_e = E / (S k)
    times the sequence's choices of e and P_e its mean score."""
    cfg, p = _setup()
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 16, cfg.d_model))
    scores, _, experts = moe_mod.route(cfg, p["router"], x)
    s, e = np.asarray(scores), np.asarray(experts)
    B, S, E = s.shape
    want = 0.0
    for b in range(B):
        f = np.bincount(e[b].ravel(), minlength=E) * E / (S * cfg.top_k)
        want += np.sum(f * s[b].mean(0)) / B
    _, aux = moe_mod.moe_apply(cfg, p, x)
    np.testing.assert_allclose(float(aux), cfg.router_aux_coef * want,
                               rtol=1e-5)


def test_layer_under_vmap_over_groups():
    """As the fused step's server half runs it: vmapped over groups."""
    cfg, p = _setup()
    cfg = dataclasses.replace(cfg, held_experts=(1, 2))
    p = {k: (v[1:3] if k.startswith("w_") else v) for k, v in p.items()}
    xs = jax.random.normal(jax.random.PRNGKey(7), (3, 2, 16, cfg.d_model))
    out, aux = jax.vmap(lambda x: moe_mod.moe_apply(cfg, p, x))(xs)
    for i in range(3):
        o, a = moe_mod.moe_apply(cfg, p, xs[i])
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(o),
                                   atol=1e-5)
        np.testing.assert_allclose(float(aux[i]), float(a), rtol=1e-6)
