"""The DeepSeek-V2-Lite cell run whole on the host CPU at a small size,
beside ``small.py``: the configuration file and the traffic are cut down,
the program's registered configuration is cut to the same widths (so that
the path's own ``program_config`` checks and applies the held experts
and the vocabulary slice), and every other part of a run is the
benchmark's own code."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os

import small

harness = small.harness
CONFIG, TRAFFIC = "deepseek-v2-lite", "lm-ep8-seq4096"

# the file cut to 4 layers (dense, then 3 MoE: split at 3), 4 of 16
# experts held from expert 4, and half of a 1,024-row vocabulary
FILE = {"num_hidden_layers": 4, "hidden_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 32,
        "v_head_dim": 32, "intermediate_size": 512,
        "moe_intermediate_size": 64, "n_routed_experts": 4,
        "first_held_expert": 4, "num_experts_per_tok": 4,
        "vocab_size": 512, "vocab_padded": 512,
        "published": {"num_hidden_layers": 27, "n_routed_experts": 16,
                      "vocab_size": 1024}}
SHAPE = {"seq_len": 64, "sequences": 4}
CUT = {("configs", CONFIG): {**FILE, "dtype": "float32", "remat": False},
       ("traffic", TRAFFIC): SHAPE}


def small_published():
    """The program's DeepSeek-V2-Lite at the widths of ``FILE``, whole: all
    16 experts and the 1,024-row vocabulary. It computes in f32 (see
    ``small.small_lm_config``)."""
    from repro.configs.base import get_config
    full = get_config("deepseek-v2-lite-16b")
    return dataclasses.replace(
        full, d_model=256, n_heads=4, n_kv_heads=4, head_dim=64,
        kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=32,
        v_head_dim=32, d_ff=512, moe_d_ff=64, n_experts=16, top_k=4,
        vocab_size=1024, dtype="float32")


@contextlib.contextmanager
def small_harness():
    import repro.configs
    load_json, get_config = harness.load_json, repro.configs.get_config

    def small_json(kind, name):
        return {**load_json(kind, name), **CUT.get((kind, name), {})}

    def small_config(name):
        return small_published() if name == "deepseek-v2-lite-16b" else \
            get_config(name)

    harness.load_json, repro.configs.get_config = small_json, small_config
    try:
        yield harness
    finally:
        harness.load_json, repro.configs.get_config = load_json, get_config


def run_small(cell_name: str, seed: int = 7, seconds: float = 0.5) -> dict:
    """One whole run of the cell on the CPU at the small size."""
    import jax
    with open(os.path.join(small.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with small_harness() as h:
        cell = h.find_cell(spec, cell_name)
        return h.run_cell(spec, cell, seed=seed, seconds=seconds,
                          trace=False, devices=jax.devices()[:1],
                          peaks=small.PEAKS)
