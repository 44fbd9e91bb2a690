"""The cell deepseek-v2-lite-ep8-seq4096, whole, on the CPU at a small
size: the program (the held-expert share through the fused S²FL step)
agrees with the plain reference within the cell's limits, and each fault
planted underneath the timed path makes ``correct`` come out false."""
import pytest

from faults import plant
from small_moe import run_small

CELL = "deepseek-v2-lite-ep8-seq4096"


def test_program_agrees_with_reference():
    out = run_small(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "server_grad_half"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    plant(fault, monkeypatch)
    out = run_small(CELL)
    assert not out["correct"], out["checks"]


def test_gmm_metrics_read_the_kernel_calls(monkeypatch):
    """At the cell's size, with remat, a step makes 96 kernel calls: 12
    for each of the two client-half MoE layers' buffer of 98,304 rows, 12
    for each of the three server-half layers' two groups of 49,152 (the
    count a traced chip run of the cell finds). The roofline share is
    the least time of the published calls of the window's steps over the
    calls' measured seconds; the device share is their seconds over busy
    time. Without calls, or with nothing published, both read nothing."""
    import json
    import os

    import flops.deepseek_v2 as fl
    from metrics import _gmm
    from run import BENCH, load_module
    with open(os.path.join(BENCH, "configs", "deepseek-v2-lite.json")) as f:
        c = json.load(f)
    step = fl.gmm_step_calls(c, 4, 4096, 3, 2)
    dF = 2 * 2048 * 1408
    assert c["remat"]
    assert [n for n, _, _ in step] == [12, 12, 24, 24, 24]
    assert [ops for _, ops, _ in step] == [12288 * dF] * 2 + [6144 * dF] * 3
    # every MoE layer: 12 matmuls over the step's 16,384 x 6 x 8 / 64 rows
    assert sum(n * ops for n, ops, _ in step) == 5 * 12 * 12288 * dF
    # without remat the forward is not run again
    assert [n for n, _, _ in fl.gmm_step_calls(
        dict(c, remat=False), 4, 4096, 3, 2)] == [9, 9, 18, 18, 18]

    monkeypatch.setattr(_gmm, "_step", {})
    roof = load_module("metrics", "roofline.moe.gmm")
    frac = load_module("metrics", "device_frac.moe.gmm")
    assert not hasattr(roof, "KERNEL") and frac.KERNEL == "held_experts_"
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    calls = [(2e-3, [])] * (96 * 3)
    ctx = {"unit": "tokens", "n_devices": 1, "work": 3 * 16384.0,
           "peaks": peaks,
           "trace": {"busy_s": 4.32, "kernel_calls": {frac.KERNEL: calls}}}
    assert roof.read(ctx) is None
    _gmm.publish(16384.0, step)
    # FLOP-bound: 400 FLOPs a byte or more against the chip's 240
    assert all(ops / moved > 240 for _, ops, moved in step)
    least = 3 * 5 * 12 * 12288 * dF / peaks["bf16_flops"]
    assert roof.read(ctx) == pytest.approx(100 * least / (288 * 2e-3))
    assert frac.read(ctx) == pytest.approx(288 * 2e-3 / 4.32)
    ctx["trace"]["kernel_calls"] = {frac.KERNEL: []}
    assert roof.read(ctx) is None and frac.read(ctx) is None
