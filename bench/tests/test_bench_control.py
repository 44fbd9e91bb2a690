"""The control of the LM cell at a small size on the CPU: the plain
reference put in the program's place, one precision below the cell's
bf16 (every matmul operand and its gradient in float8_e4m3fn with a
per-tensor scale), read against the reference, fails the cell's limits.
(The VGG-16 cell's control, the reference at 'high', only differs on a
TPU: the CPU computes f32 one way whatever the precision asked.)"""
import json
import os

import jax

from small import ROOT, small_harness

import compare  # noqa: E402  (on the path once small is imported)

CELL = "internlm2-1.8b-seq4096"


def test_control_fails_the_limits():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = next(w for w in json.load(f)["workloads"]
                    if w["name"] == CELL)
    with small_harness() as h:
        path = h.load_module("paths", h.load_json("traffic",
                                                  cell["traffic"])["path"])
        runner = path.Runner(h.load_json("configs", cell["config"]),
                             h.load_json("traffic", cell["traffic"]),
                             cell=CELL, seed=11, devices=jax.devices()[:1],
                             log=lambda *a: None)
        runner.setup()
        runner.free()
        ref = runner.reference()
        numbers = compare.training_numbers(
            runner.reference(**runner.VARIANTS["control"]), ref)
    checks = compare.checks(numbers, compare.limits_of(CELL))
    assert any(c["value"] > c["limit"] for c in checks), checks
