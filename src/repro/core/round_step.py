"""Fused SPMD S²FL round step — the pod-scale form of Algorithm 2.

Mapping: the global batch dim hosts the x participating device cohorts
(data-parallel shards), as the paper's clients. One jitted step performs:

  client-half forward  (batch sharded over `data`)
  balance permutation  (jnp.take over the global batch -> all-to-all; this
                        IS the paper's feature upload + Eq.2 regroup)
  per-group server half (vmap over G groups = G server-side copies)
  combined loss (Eq. 3), grad                     (VJP of the permutation
                        = the paper's gradient return, Step 7)
  SGD update; XLA's data-axis psum of grads is the E=1 fusion of per-copy
  updates + Algorithm-1 weighted aggregation (equal cohort weights).

Equivalence with the host engine at E=1 is asserted in tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.api import SplitModel
from repro.models.sharding import batch_spec, model_param_specs


def make_s2fl_loss(cfg, split: int, n_groups: int, dp_axes=None,
                   group_members: int = 1):
    """dp_axes: mesh axes the batch shards over (enables explicit sharding
    constraints around the balance permutation at pod scale; None for
    host/test execution). group_members: clients (cohorts) per balance
    group — Eq. 3 sums per-client losses, so the fused per-group CE mean
    is scaled by the member count (engine-equivalence tested)."""
    model = SplitModel(cfg)

    def csts(x, spec):
        if dp_axes is None:
            return x
        return jax.lax.with_sharding_constraint(x, spec)

    compute_dtype = jnp.dtype(cfg.dtype)

    @jax.custom_vjp
    def _grad_cast(x):
        return x

    def _gc_fwd(x):
        return x, None

    def _gc_bwd(_, g):
        # keep the permutation-backward collective in the compute dtype
        # (otherwise the scatter-add accumulates f32 — 2x ICI bytes)
        return (g.astype(compute_dtype),)

    _grad_cast.defvjp(_gc_fwd, _gc_bwd)

    def loss_fn(params, batch):
        with jax.named_scope("client_fwd"):
            feats = model.client_forward(params, batch, split, train=True)
        with jax.named_scope("regroup"):
            h = _grad_cast(feats["h"])
            h = jnp.take(h, batch["perm"], axis=0)           # all-to-all
            labels = jnp.take(batch["labels"], batch["perm"], axis=0)
            tokens = jnp.take(batch["tokens"], batch["perm"], axis=0)
            B = h.shape[0]
            gb = B // n_groups
            hg = h.reshape(n_groups, gb, *h.shape[1:])
            lg = labels.reshape(n_groups, gb, *labels.shape[1:])
            tg = tokens.reshape(n_groups, gb, *tokens.shape[1:])
            # keep the per-group batch dim on the data axes through the
            # permutation (otherwise SPMD replicates the server half)
            hg = csts(hg, P(None, dp_axes, *([None] * (h.ndim - 1))))
            lg = csts(lg, P(None, dp_axes, *([None] * (labels.ndim - 1))))
            tg = csts(tg, P(None, dp_axes, *([None] * (tokens.ndim - 1))))

        def group_loss(hh, ll, tt):
            l, _ = model.server_loss(
                params, {"h": hh, "aux": jnp.zeros((), jnp.float32)},
                {"tokens": tt, "labels": ll}, split, train=True)
            return l

        with jax.named_scope("server_loss"):
            losses = jax.vmap(group_loss)(hg, lg, tg)        # G copies
        return losses.mean() * group_members + feats["aux"]

    return loss_fn


def make_s2fl_train_step(cfg, split: int, n_groups: int, lr: float,
                         dp_axes=None, group_members: int = 1):
    loss_fn = make_s2fl_loss(cfg, split, n_groups, dp_axes=dp_axes,
                             group_members=group_members)

    def s2fl_round_step(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        with jax.named_scope("sgd"):
            params = jax.tree.map(
                lambda w, g: (w - lr * g.astype(w.dtype)).astype(w.dtype),
                params, grads)
        return params, loss

    return s2fl_round_step


def train_step_shardings(cfg, mesh, batch_abstract):
    """(in_shardings, out_shardings) for jax.jit over (params, batch)."""
    pspecs = model_param_specs(cfg, mesh)
    bspecs = {}
    for k, v in batch_abstract.items():
        if k == "perm":
            bspecs[k] = P(None)
        else:
            bspecs[k] = batch_spec(mesh, v.ndim, batch_size=v.shape[0])
    to_sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    in_sh = (to_sh(pspecs), to_sh(bspecs))
    out_sh = (to_sh(pspecs), NamedSharding(mesh, P()))
    return in_sh, out_sh
