"""Path: the fused S²FL round step (``paths/fused_step.py``, whose
``Runner`` this is) of a mixture-of-experts decoder held as one chip's
expert-parallel share: the program's configuration is given the file's
depth, its held range of routed experts (``n_routed_experts`` from
``first_held_expert``), its vocabulary slice and its published MoE and
rope settings, each checked against the file. At set-up it publishes one
step's calls of the held experts' grouped-matmul kernels, with their
FLOPs and bytes (``flops/<family>.gmm_step_calls``), for the kernels'
readers (``metrics/_gmm.py``). The traffic keys are ``fused_step``'s.

A program without a held-expert range cannot run the share, and
``program_config`` refuses it at once.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_paths_fused_step_for_ep",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "fused_step.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

# file key -> program config attribute, equal in the file and the program
WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "intermediate_size": "d_ff", "kv_lora_rank": "kv_lora_rank",
          "qk_nope_head_dim": "qk_nope_head_dim",
          "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
          "moe_intermediate_size": "moe_d_ff",
          "n_shared_experts": "n_shared_experts",
          "num_experts_per_tok": "top_k", "rope_theta": "rope_theta",
          "rms_norm_eps": "norm_eps", "norm_topk_prob": "norm_topk_prob",
          "aux_loss_alpha": "router_aux_coef"}
ROPE = {"factor": "factor",
        "original_max_position_embeddings":
            "original_max_position_embeddings",
        "beta_fast": "beta_fast", "beta_slow": "beta_slow",
        "mscale": "mscale", "mscale_all_dim": "mscale_all_dim"}


def _check(name, what, program, file):
    if program != file:
        raise ValueError(f"{name}: program {what}={program!r} != file "
                         f"{file!r}")


def program_config(c: dict):
    """The program's registered configuration as this chip's share: the
    file's depth, held experts and vocabulary slice; every other setting
    must equal the file's."""
    from repro.configs import get_config
    full = get_config(c["program_config"])
    if "held_experts" not in {f.name for f in dataclasses.fields(full)}:
        raise RuntimeError(
            f"{c['name']}: this program has no held-expert range "
            "(ModelConfig.held_experts), so it cannot run one chip's share "
            "of an expert-parallel layer")
    for key, attr in WIDTHS.items():
        _check(c["name"], attr, getattr(full, attr), c[key])
    rs = full.rope_scaling
    for key, attr in ROPE.items():
        _check(c["name"], f"rope_scaling.{attr}", getattr(rs, attr, None),
               c["rope_scaling"][key])
    pub = c["published"]
    _check(c["name"], "n_experts", full.n_experts, pub["n_routed_experts"])
    _check(c["name"], "vocab_size", full.vocab_size, pub["vocab_size"])
    _check(c["name"], "mla", full.mla, c["q_lora_rank"] is None)
    L, k = c["num_hidden_layers"], c["first_k_dense_replace"]
    _check(c["name"], "ffn_pattern", full.ffn_pattern[:L],
           ("dense",) * k + ("moe",) * (L - k))
    return dataclasses.replace(
        full, n_layers=L, block_pattern=full.block_pattern[:L],
        ffn_pattern=full.ffn_pattern[:L], vocab_size=c["vocab_size"],
        held_experts=(c["first_held_expert"], c["n_routed_experts"]),
        remat=c["remat"], dtype=c["dtype"], param_dtype=c["param_dtype"])


_base.program_config = program_config


class Runner(_base.Runner):
    def setup(self):
        super().setup()
        from metrics import _gmm
        B, S = self.shape
        _gmm.publish(B * S, self.flops.gmm_step_calls(
            self.c, B, S, self.split, self.n_groups))
