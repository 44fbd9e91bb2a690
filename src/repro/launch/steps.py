"""Step builders + abstract input specs for every (arch × input shape).

Shapes (assigned):
  train_4k     seq 4,096   global_batch 256   -> fused S²FL round step
  prefill_32k  seq 32,768  global_batch 32    -> prefill (cache build)
  decode_32k   seq 32,768  global_batch 128   -> one-token serve step
  long_500k    seq 524,288 global_batch 1     -> one-token serve step
                                                 (sub-quadratic archs only)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.round_step import make_s2fl_train_step, train_step_shardings
from repro.models import transformer as tf_mod
from repro.models.frontends import frontend_embed_spec
from repro.models.sharding import (batch_spec, cache_specs, data_axes,
                                   model_param_specs)

SHAPES = {
    "train_4k": {"seq": 4096, "batch": 256, "kind": "train"},
    "prefill_32k": {"seq": 32768, "batch": 32, "kind": "prefill"},
    "decode_32k": {"seq": 32768, "batch": 128, "kind": "decode"},
    "long_500k": {"seq": 524288, "batch": 1, "kind": "decode"},
}

# S²FL defaults at pod scale: 16 cohorts (one per data shard), 4 balance
# groups, one of the plan's split points.
DEFAULT_GROUPS = 4


def long_context_ok(cfg) -> bool:
    """long_500k runs for SSM/hybrid and sliding-window dense archs; pure
    full-attention archs are skipped (DESIGN.md §4)."""
    return cfg.arch_type in ("ssm", "hybrid") or cfg.sliding_window > 0


def shape_applicable(cfg, shape: str) -> bool:
    if shape == "long_500k":
        return long_context_ok(cfg)
    return True


def default_split(cfg) -> int:
    from repro.core.split import default_plan
    return default_plan(cfg.n_layers).split_points[-1]


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------
def train_inputs(cfg, *, batch: int, seq: int):
    i32 = jnp.int32
    specs = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), i32),
        "labels": jax.ShapeDtypeStruct((batch, seq), i32),
        "perm": jax.ShapeDtypeStruct((batch,), i32),
    }
    if cfg.frontend:
        specs["prefix"] = frontend_embed_spec(cfg, batch)
    return specs


def prefill_inputs(cfg, *, batch: int, seq: int):
    specs = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    if cfg.frontend:
        specs["prefix"] = frontend_embed_spec(cfg, batch)
    return specs


def decode_inputs(cfg, *, batch: int, seq: int):
    caches = jax.eval_shape(
        functools.partial(tf_mod.init_caches, cfg, batch, seq))
    return {
        "token": jax.ShapeDtypeStruct((batch, 1), jnp.int32),
        "index": jax.ShapeDtypeStruct((), jnp.int32),
        "caches": caches,
    }


def input_specs(cfg, shape: str):
    s = SHAPES[shape]
    fn = {"train": train_inputs, "prefill": prefill_inputs,
          "decode": decode_inputs}[s["kind"]]
    return fn(cfg, batch=s["batch"], seq=s["seq"])


# ---------------------------------------------------------------------------
# step builders (returns (fn, in_shardings, out_shardings, abstract_args))
# ---------------------------------------------------------------------------
def abstract_model_params(cfg, mesh):
    from repro.models.transformer import abstract_model
    return abstract_model(cfg)


def build_train_step(cfg, mesh, *, split=None, n_groups: int = DEFAULT_GROUPS,
                     lr: float = 0.01, shape: str = "train_4k",
                     remat: bool = True, scan_layers=None,
                     remat_policy=None):
    """scan_layers None -> use the config's flag. Scan keeps compile time
    O(#block kinds) (mandatory for kimi-k2), but XLA's cost_analysis
    counts while-loop bodies ONCE — the dry-run corrects flops by
    two-point depth extrapolation for scanned configs (dryrun.py)."""
    import dataclasses
    repl = {}
    if remat and not cfg.remat:
        repl["remat"] = True
    if scan_layers is not None and scan_layers != cfg.scan_layers:
        repl["scan_layers"] = scan_layers
    if remat_policy is not None:
        repl["remat_policy"] = remat_policy
    if repl:
        cfg = dataclasses.replace(cfg, **repl)
    split = split if split is not None else default_split(cfg)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_cohorts = 1
    for a in data_axes(mesh):
        n_cohorts *= axis_sizes[a]
    step = make_s2fl_train_step(cfg, split, n_groups, lr,
                                dp_axes=data_axes(mesh),
                                group_members=max(1, n_cohorts // n_groups))
    batch_abs = input_specs(cfg, shape)
    in_sh, out_sh = train_step_shardings(cfg, mesh, batch_abs)
    params_abs = abstract_model_params(cfg, mesh)
    return step, in_sh, out_sh, (params_abs, batch_abs)


def build_prefill_step(cfg, mesh, *, shape: str = "prefill_32k",
                       max_len=None):
    s = SHAPES[shape]
    batch, seq = s["batch"], s["seq"]
    # modality prefix tokens occupy cache slots too
    max_len = max_len or (seq + (cfg.n_frontend_tokens if cfg.frontend
                                 else 0))

    def step(params, batch_in):
        logits, caches, n = tf_mod.prefill(cfg, params, batch_in["tokens"],
                                           max_len,
                                           batch_in.get("prefix"))
        return logits, caches

    batch_abs = input_specs(cfg, shape)
    pspecs = model_param_specs(cfg, mesh)
    to_sh = lambda t: jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), t,
        is_leaf=lambda x: isinstance(x, P))
    bspec = {k: NamedSharding(mesh, batch_spec(mesh, v.ndim,
                                               batch_size=v.shape[0]))
             for k, v in batch_abs.items()}
    caches_abs = jax.eval_shape(
        functools.partial(tf_mod.init_caches, cfg, batch, max_len))
    cspecs = cache_specs(cfg, mesh, caches_abs, batch)
    out_sh = (NamedSharding(mesh, batch_spec(mesh, 3, batch_size=batch)),
              to_sh(cspecs))
    params_abs = abstract_model_params(cfg, mesh)
    return step, (to_sh(pspecs), bspec), out_sh, (params_abs, batch_abs)


def build_decode_step(cfg, mesh, *, shape: str = "decode_32k"):
    s = SHAPES[shape]
    batch, seq = s["batch"], s["seq"]

    def step(params, batch_in):
        logits, caches = tf_mod.decode_step(cfg, params, batch_in["token"],
                                            batch_in["caches"],
                                            batch_in["index"])
        return logits, caches

    batch_abs = input_specs(cfg, shape)
    pspecs = model_param_specs(cfg, mesh)
    to_sh = lambda t: jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), t,
        is_leaf=lambda x: isinstance(x, P))
    cspecs = cache_specs(cfg, mesh, batch_abs["caches"], batch)
    bspec = {
        "token": NamedSharding(mesh, batch_spec(mesh, 2, batch_size=batch)),
        "index": NamedSharding(mesh, P()),
        "caches": to_sh(cspecs),
    }
    out_sh = (NamedSharding(mesh, batch_spec(mesh, 3, batch_size=batch)),
              to_sh(cspecs))
    params_abs = abstract_model_params(cfg, mesh)
    return step, (to_sh(pspecs), bspec), out_sh, (params_abs, batch_abs)


def build_step(cfg, mesh, shape: str, **kw):
    kind = SHAPES[shape]["kind"]
    if kind == "train":
        return build_train_step(cfg, mesh, shape=shape, **kw)
    if kind == "prefill":
        return build_prefill_step(cfg, mesh, shape=shape, **kw)
    return build_decode_step(cfg, mesh, shape=shape, **kw)
