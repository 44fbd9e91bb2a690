"""Size / FLOPs accounting (the paper uses `thop`; this is the JAX
equivalent). Produces Figure-3 style per-portion sizes and FLOPs and the
Eq.-1 inputs (|Wc|, q, Fc, Fs) for the simulator.

Transformer costs are analytic (per sample of sequence length S);
CNN unit costs come from XLA's own cost model (``compiled.cost_analysis``
on a per-unit lowering for the host CPU, which is exact for convs),
measured once and committed as ``cnn_unit_costs.json``.
"""
from __future__ import annotations

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.models.api import SplitModel, get_subtree
from repro.models.params import count_params


# ---------------------------------------------------------------------------
# parameter counts per segment
# ---------------------------------------------------------------------------
def segment_param_counts(model: SplitModel) -> dict:
    defs = model.defs()
    return {name: count_params(get_subtree(defs, path))
            for name, path in model.segments()}


def client_portion_size(model: SplitModel, split: int) -> float:
    counts = segment_param_counts(model)
    return float(sum(counts[n] for n in model.client_segments(split)))


def full_size(model: SplitModel) -> float:
    return float(sum(segment_param_counts(model).values()))


# ---------------------------------------------------------------------------
# forward FLOPs per unit, per sample
# ---------------------------------------------------------------------------
def _attn_fwd_flops(cfg, S: int) -> float:
    d, H = cfg.d_model, cfg.n_heads
    if cfg.mla:
        Dn, Dr, Dv, R = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
        proj = 2 * S * d * (H * (Dn + Dr) + R + Dr) \
            + 2 * S * R * H * (Dn + Dv) + 2 * S * H * Dv * d
        attn = 4 * S * S * H * (Dn + Dr) / 2            # causal half
        return proj + attn
    K, D = cfg.n_kv_heads, cfg.head_dim
    proj = 2 * S * d * D * (H + 2 * K) + 2 * S * H * D * d
    eff = min(S, cfg.sliding_window) if cfg.sliding_window else S
    attn = 4 * S * eff * H * D / (1 if cfg.sliding_window else 2)
    return proj + attn


def _mlp_fwd_flops(cfg, S: int, d_ff=None) -> float:
    ff = d_ff if d_ff is not None else cfg.d_ff
    return 6.0 * S * cfg.d_model * ff


def _moe_fwd_flops(cfg, S: int) -> float:
    routed = 6.0 * S * cfg.d_model * cfg.moe_d_ff * cfg.top_k
    shared = 6.0 * S * cfg.d_model * cfg.moe_d_ff * cfg.n_shared_experts
    router = 2.0 * S * cfg.d_model * cfg.n_experts
    return routed + shared + router


def _ssm_fwd_flops(cfg, S: int) -> float:
    d, di, N, Hs, P = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                       cfg.n_ssm_heads, cfg.ssm_head_dim)
    proj = 2 * S * d * (2 * di + 2 * N + Hs) + 2 * S * di * d
    conv = 2 * S * cfg.ssm_conv * (di + 2 * N)
    l = cfg.ssm_chunk
    # per chunk: CB^T (l²N) + W·x (l²·Hs·P) + state in/out (2·l·Hs·P·N)
    chunks = S / l
    ssd = chunks * (2 * l * l * N + 2 * l * l * Hs * P
                    + 4 * l * Hs * P * N)
    return proj + conv + ssd


def transformer_unit_flops(cfg, S: int) -> list:
    """Per-block fwd FLOPs for one sample of length S."""
    out = []
    for mixer, ffn in cfg.pattern():
        f = 0.0
        if mixer == "ssm":
            f += _ssm_fwd_flops(cfg, S)
        else:
            import dataclasses as _dc
            # 'attn' layers are global even when cfg carries a window
            # (gemma3's 5 local : 1 global pattern)
            c = cfg if mixer == "swa" else _dc.replace(cfg, sliding_window=0)
            f += _attn_fwd_flops(c, S)
        if ffn == "dense":
            f += _mlp_fwd_flops(cfg, S)
        elif ffn == "moe":
            f += _moe_fwd_flops(cfg, S)
        out.append(f)
    return out


def head_flops(cfg, S: int) -> float:
    return 2.0 * S * cfg.d_model * cfg.vocab_padded


CNN_COST_TABLE = os.path.join(os.path.dirname(__file__),
                              "cnn_unit_costs.json")


def measure_cnn_unit_costs(cfg) -> tuple:
    """(fwd_flops, out_feature_elems) per unit, per sample, from XLA's
    cost analysis of each unit lowered for the host CPU. The CPU is named
    explicitly: another backend's compiler counts other FLOPs for the
    same convolution (the TPU's counts run 1.3-2.1x higher), and these
    numbers drive the Eq.-1 clock and the split scheduler."""
    from repro.models.cnn import cnn_units
    cpu = SingleDeviceSharding(jax.devices("cpu")[0])
    units, _ = cnn_units(cfg)
    params_abs = SplitModel(cfg).abstract()
    x = jax.ShapeDtypeStruct((1, cfg.image_size, cfg.image_size,
                              cfg.in_channels), jnp.float32, sharding=cpu)
    out = []
    for i, (_, apply_i) in enumerate(units):
        p = jax.tree.map(
            lambda d: jax.ShapeDtypeStruct(d.shape, jnp.float32,
                                           sharding=cpu),
            params_abs["units"][i])
        cost = jax.jit(apply_i).lower(p, x).compile().cost_analysis()
        y = jax.eval_shape(apply_i, p, x)
        x = jax.ShapeDtypeStruct(y.shape, y.dtype, sharding=cpu)
        out.append((float(cost.get("flops", 0.0)),
                    float(math.prod(y.shape[1:]))))
    return tuple(out)


@functools.cache
def _cost_table() -> dict:
    with open(CNN_COST_TABLE) as f:
        return json.load(f)


def _cnn_unit_costs(cfg) -> tuple:
    """(fwd_flops, out_feature_elems) per unit, from the committed table
    that ``measure_cnn_unit_costs`` generated, so the simulated clock is
    the same on every backend. Regenerate it with
    ``python -m repro.utils.flops``."""
    from repro.configs import get_config
    if cfg != get_config(cfg.name):
        raise ValueError(f"{cfg.name}: no cost table for a modified CNN "
                         "config; add it to cnn_unit_costs.json")
    return tuple(tuple(u) for u in _cost_table()[cfg.name])


# ---------------------------------------------------------------------------
# Eq.-1 inputs for a given split
# ---------------------------------------------------------------------------
def split_costs(model: SplitModel, split: int, *, seq_len: int = 0) -> dict:
    """Per-sample Eq.-1 quantities for split s:
    wc_size (elements), feat_size q (elements/sample),
    fc / fs (fwd+bwd FLOPs per sample, bwd = 2x fwd)."""
    cfg = model.cfg
    counts = segment_param_counts(model)
    wc = client_portion_size(model, split)
    if model.is_cnn:
        unit_costs = _cnn_unit_costs(cfg)
        fwd = [f for f, _ in unit_costs]
        feat = unit_costs[split - 1][1] if split >= 1 else float(
            cfg.image_size ** 2 * cfg.in_channels)
        head = 2.0 * unit_costs[-1][1]
    else:
        S = seq_len + (cfg.n_frontend_tokens if cfg.frontend else 0)
        fwd = transformer_unit_flops(cfg, S)
        feat = float(S * cfg.d_model)
        head = head_flops(cfg, S)
    fc = 3.0 * sum(fwd[:split])
    fs = 3.0 * (sum(fwd[split:]) + head)
    return {"wc_size": wc, "feat_size": feat, "fc": fc, "fs": fs,
            "w_size": float(sum(counts.values())),
            "f_full": 3.0 * (sum(fwd) + head)}


def model_flops_6nd(cfg, n_tokens: int) -> float:
    """MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) for the roofline
    useful-compute ratio."""
    model = SplitModel(cfg)
    counts = segment_param_counts(model)
    total = sum(counts.values())
    if cfg.n_experts:
        # active = total - routed expert params + top_k/E * routed
        routed = 0
        for name, path in model.segments():
            if not name.startswith("block:"):
                continue
            i = int(name.split(":")[1])
            if cfg.pattern()[i][1] == "moe":
                E, F, d = cfg.n_experts, cfg.moe_d_ff, cfg.d_model
                routed += 3 * E * d * F
        active = total - routed + routed * cfg.top_k / cfg.n_experts
        return 6.0 * active * n_tokens
    return 6.0 * total * n_tokens


def _write_cost_table():
    from repro.configs import get_config, list_configs
    table = {name: measure_cnn_unit_costs(get_config(name))
             for name in list_configs()
             if hasattr(get_config(name), "family")}
    with open(CNN_COST_TABLE, "w") as f:
        f.write("{\n" + ",\n".join(f"{json.dumps(k)}: {json.dumps(v)}"
                                   for k, v in table.items()) + "\n}\n")


if __name__ == "__main__":
    _write_cost_table()
