"""The trace reduction: busy and idle time, device time per operation,
and idle gaps labelled by the host's innermost event."""
import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce  # noqa: E402

US = 1000.0     # ns


def _hand_trace():
    # window 0..100 us; device busy 10-30 (fusion.1), 25-40 (dot.2),
    # 70-80 (fusion.1), 95-120 (dot.2, cut at the window's end)
    ops = [["%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p)",
            10 * US, 20 * US],
           ["%dot.2 = f32[8,8]{1,0} dot(f32[8,128]{1,0} %a, f32[128,8] %b)",
            25 * US, 15 * US],
           ["%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p)",
            70 * US, 10 * US],
           ["%int8_roundtrip_pallas.3 = f32[2560,256]{1,0} custom-call("
            "f32[2560,256]{1,0} %x), custom_call_target=\"tpu_custom_call\"",
            95 * US, 25 * US],
           ["%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p)",
            -20 * US, 10 * US]]
    modules = [["jit_step(123)", 5 * US, 40 * US],
               ["jit_codec(456)", 60 * US, 70 * US]]
    host = [["bench.window", 0.0, 100 * US],
            ["bench.call", 0.0, 50 * US], ["bench.call", 50 * US, 50 * US],
            ["$engine.py:500 run_round", 1 * US, 48 * US],
            ["$engine.py:242 _sample_batch", 2 * US, 7 * US],
            ["$engine.py:500 run_round", 51 * US, 48 * US],
            ["$aggregation.py:30 aggregate", 45 * US, 3 * US]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": {"python": host, "other": [["x", 0.0, 1e9]]}}


def test_busy_ops_and_gaps():
    r = trace_reduce.reduce(_hand_trace(),
                            kernels=("int8_roundtrip_pallas",))
    assert r["window_s"] == pytest.approx(100e-6)
    # union: 10-40, 70-80, 95-100 -> 45 us
    assert r["busy_s"] == pytest.approx(45e-6)
    ops = dict(r["device_ops"])
    assert ops["jit_step:fusion.1 f32[8,128]"] == pytest.approx(20e-6)
    assert ops["jit_codec:fusion.1 f32[8,128]"] == pytest.approx(10e-6)
    assert ops["jit_step:dot.2 f32[8,8]"] == pytest.approx(15e-6)
    assert dict(r["programs"]) == pytest.approx(
        {"jit_step": 35e-6, "jit_codec": 15e-6})
    # the kernel call is cut at the window's end: 5 us
    (secs, shp), = r["kernel_calls"]["int8_roundtrip_pallas"]
    assert secs == pytest.approx(5e-6)
    assert shp == [("f32", (2560, 256)), ("f32", (2560, 256))]
    gaps = dict(r["idle_gaps"])
    # 0-10 (midpoint 5: _sample_batch), 40-70 (55: run_round),
    # 80-95 (87.5: run_round)
    assert gaps["$engine.py:242 _sample_batch"] == pytest.approx(10e-6)
    assert gaps["$engine.py:500 run_round"] == pytest.approx(45e-6)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_no_window_is_an_error():
    t = _hand_trace()
    t["host"]["python"] = t["host"]["python"][1:]
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(t)


def test_recorded_trace():
    """0.3 s of a traced window of the VGG-16 federation with int8/top-k
    codecs on a TPU v5 lite (cut from a full trace; the window annotation
    cut with it): the reduction finds the programs, the host's idle gaps
    and the int8 kernel's calls with their shapes."""
    with gzip.open(os.path.join(HERE, "data",
                                "trace_vgg16_async_0.3s.json.gz"), "rt") as f:
        t = json.load(f)
    r = trace_reduce.reduce(t, kernels=("int8_roundtrip_pallas",
                                        "sparse_combine_pallas"))
    assert r["window_s"] == pytest.approx(0.3)
    assert r["busy_s"] == pytest.approx(0.021310689, rel=1e-6)
    assert sum(s for _, s in r["idle_gaps"]) <= r["window_s"] - r["busy_s"]
    assert dict(r["idle_gaps"])["DevicePut"] == pytest.approx(0.0841784,
                                                              rel=1e-5)
    assert [p for p, _ in r["programs"][:3]] == ["jit_one", "jit__lambda",
                                                 "jit_step"]
    calls = r["kernel_calls"]["int8_roundtrip_pallas"]
    assert [shp[0] for _, shp in calls] == [
        ("f32", (24576, 256)), ("f32", (2048, 256)), ("f32", (6144, 256))]
    assert r["kernel_calls"]["sparse_combine_pallas"] == []
