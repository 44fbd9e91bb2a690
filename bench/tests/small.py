"""A cell of the benchmark run whole on the host CPU at a small size, for
the tests: the look for a chip is skipped, the program's configuration and
the traffic are cut down, and every other part of a run (set-up through
the window's own call, the window, the check against the plain reference
with the cell's own limits) is the benchmark's own code."""
from __future__ import annotations

import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as harness  # noqa: E402

# what each config and traffic is cut to here
SMALL = {
    ("configs", "internlm2-1.8b"): {
        "num_hidden_layers": 2, "hidden_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
        "intermediate_size": 1024, "vocab_size": 512, "vocab_padded": 512},
    ("traffic", "lm-seq4096"): {"seq_len": 128, "sequences": 4},
    ("traffic", "paper-fed-sync"): {"n_train": 1000, "clients": 10,
                                    "per_round": 4, "batch_size": 4,
                                    "cycle_rounds": 4},
}
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def small_lm_config():
    """The program's InternLM2 at the widths of SMALL. It computes in f32
    (the cell computes in bf16): at 512 tokens a step the bf16 rounding of
    the loss is not averaged down as it is at the cell's 8,192, and in f32
    the program and the reference must agree far inside the limits."""
    from repro.configs import get_config, make_reduced
    return make_reduced(get_config("internlm2-1.8b"))


@contextlib.contextmanager
def small_harness():
    load_json, load_module = harness.load_json, harness.load_module

    def small_json(kind, name):
        return {**load_json(kind, name), **SMALL.get((kind, name), {})}

    def small_module(kind, name):
        mod = load_module(kind, name)
        if kind == "paths" and name == "fused_step":
            mod.program_config = lambda c: small_lm_config()
        return mod

    harness.load_json, harness.load_module = small_json, small_module
    try:
        yield harness
    finally:
        harness.load_json, harness.load_module = load_json, load_module


def run_small(cell_name: str, seed: int = 7, seconds: float = 0.5) -> dict:
    """One whole run of the cell on the CPU at the small size."""
    import jax
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with small_harness() as h:
        cell = h.find_cell(spec, cell_name)
        return h.run_cell(spec, cell, seed=seed, seconds=seconds,
                          trace=False, devices=jax.devices()[:1],
                          peaks=PEAKS)
