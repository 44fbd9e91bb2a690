"""The benchmark's yardsticks against hand counts: FLOPs of a VGG-16
convolution unit and an InternLM2 layer, bytes of the two comm_fused
kernels at one shape, the peaks table, and the end-to-end readers."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import run as harness  # noqa: E402
from flops import comm_fused  # noqa: E402
from flops import internlm2 as transformer  # noqa: E402
from flops import vgg as cnn  # noqa: E402

LM = {"num_hidden_layers": 8, "hidden_size": 2048,
      "num_attention_heads": 16, "num_key_value_heads": 8, "head_dim": 128,
      "intermediate_size": 8192, "vocab_padded": 92544}


def test_vgg16_first_conv_unit():
    # 32 x 32 outputs, 3 -> 64 channels, 3 x 3 taps, 2 FLOPs a MAC
    assert cnn.conv_fwd_flops(32, 3, 64) == 2 * 1024 * 3 * 64 * 9 == 3538944


def test_vgg16_train_flops_per_sample():
    c = harness.load_json("configs", "vgg16")
    per_layer = [cnn.conv_fwd_flops(*l) for l in cnn.vgg_layers(c)]
    assert len(per_layer) == 13
    # stage by stage: 32x32 (3->64, 64->64), 16x16 (64->128, 128->128),
    # 8x8 (128->256, 2 x 256->256), 4x4 (256->512, 2 x 512->512),
    # 2x2 (3 x 512->512)
    mac = (1024 * 9 * (3 * 64 + 64 * 64) + 256 * 9 * (64 * 128 + 128 * 128)
           + 64 * 9 * (128 * 256 + 2 * 256 * 256)
           + 16 * 9 * (256 * 512 + 2 * 512 * 512) + 4 * 9 * 3 * 512 * 512)
    assert sum(per_layer) == 2 * mac
    head = 2 * 512 * 10
    assert cnn.train_flops_per_sample(c) == 3 * (2 * mac + head) \
        - per_layer[0]


def test_internlm2_layer():
    S = 4096
    qkvo = 2 * S * 2048 * (16 * 128 + 2 * 8 * 128) + 2 * S * 16 * 128 * 2048
    scores_and_values = 2 * (2 * S * S * 16 * 128) // 2     # causal half
    swiglu = 3 * 2 * S * 2048 * 8192
    assert transformer.layer_fwd_flops(LM, S) == \
        qkvo + scores_and_values + swiglu
    head = 2 * S * 2048 * 92544
    assert transformer.train_flops_per_token(LM, S) == pytest.approx(
        3 * (8 * (qkvo + scores_and_values + swiglu) + head) / S)


def test_comm_fused_kernels():
    # 10 devices x 256 groups of 256 f32 values: read and write once
    assert comm_fused.int8_roundtrip(2560, 256) == {
        "bytes": 2 * 2560 * 256 * 4, "ops": 9 * 2560 * 256}
    # (10, 524288) f32: y and mask read, delivered and residual written
    assert comm_fused.sparse_combine(10, 524288) == {
        "bytes": 4 * 10 * 524288 * 4, "ops": 3 * 10 * 524288}
    peaks = harness.peaks_of("TPU v5 lite")
    t = comm_fused.least_seconds(comm_fused.sparse_combine(10, 524288),
                                 peaks)
    assert t == pytest.approx(4 * 10 * 524288 * 4 / 819e9)


def test_unknown_device_kind_raises():
    assert harness.peaks_of("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_of("TPU v9000")


def test_end_to_end_readers():
    ctx = {"setup_s": 42.0, "window_s": 2.0, "work": 640.0,
           "unit": "samples",
           "flops_per_unit": 1e9, "peaks": {"bf16_flops": 1e12},
           "n_devices": 1, "compiles_in_window": 0, "trace": None}
    read = {n: harness.load_module("metrics", n).read for n in (
        "setup_s", "samples_per_s", "tokens_per_s", "mfu.cnn", "mfu.lm",
        "compiles_in_window", "idle_frac.cnn")}
    assert read["setup_s"](ctx) == 42.0
    assert read["samples_per_s"](ctx) == 320.0
    assert read["tokens_per_s"](ctx) is None
    assert read["mfu.cnn"](ctx) == pytest.approx(100 * 640e9 / 2.0 / 1e12)
    assert read["mfu.lm"](ctx) is None
    assert read["compiles_in_window"](ctx) == 0
    assert read["idle_frac.cnn"](ctx) is None       # untraced run


def test_reference_codecs():
    """The reference's codecs on a hand example: int8 over one group maps
    min and max to themselves and rounds the rest to the step; top-k
    keeps the largest magnitudes; error feedback carries the residual."""
    import numpy as np

    from reference import vgg
    x = np.array([-1.0, 0.0, 0.3, 1.54], np.float32)
    step = 2.54 / 254
    want = -1.0 + step * np.round((x + 1.0) / step)
    np.testing.assert_allclose(np.asarray(vgg.int8_roundtrip(x)), want,
                               rtol=0, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(vgg.topk_roundtrip(np.array([0.1, -3.0, 2.0, 0.5],
                                               np.float32), 0.5)),
        [0.0, -3.0, 2.0, 0.0])
    link = vgg.Link("topk", frac=0.5, feedback=True)
    first = np.asarray(link(7, np.array([1.0, 4.0, -2.0, 0.5], np.float32)))
    np.testing.assert_array_equal(first, [0.0, 4.0, -2.0, 0.0])
    second = np.asarray(link(7, np.zeros(4, np.float32)))
    np.testing.assert_array_equal(second, [1.0, 0.0, 0.0, 0.5])
