"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a public kernel wrapper at real widths for
one chip of a ``v5e:2x2`` topology that the installed TPU compiler
describes without a chip attached, and asserts the compiled program holds
a Mosaic kernel (``tpu_custom_call``). That catches what interpret mode
cannot: block shapes the TPU tiling rules refuse, and kernels that need
more VMEM than a core has. It also shows that ``repro.kernels.backend``
picks the compiled kernel when the program is lowered for a TPU.

The topology is described inside a module fixture, never while a module
is imported, and the fixture skips where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.utils.flops import _cnn_unit_costs

D_COHORT = 10                   # devices per round (paper Sec. 5.1)
BATCH = 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _vgg_cut_values():
    """Widest VGG-16 unit output at batch 32: one device's row of the
    stacked cohort buffer the fused codecs take."""
    return int(max(e for _, e in _cnn_unit_costs(get_config("vgg16")))) \
        * BATCH


def test_int8_quantize_compiles_at_vgg_cut(one_chip):
    from repro.kernels.int8_quant import int8_quantize
    n = _vgg_cut_values()
    _compile(one_chip, lambda x: int8_quantize(x)[:3],
             ((n // 64, 64), jnp.float32))


def test_int8_dequantize_compiles_at_vgg_cut(one_chip):
    from repro.kernels.int8_quant import int8_dequantize
    from repro.kernels.int8_quant.ops import GROUP
    n = _vgg_cut_values()
    r = n // GROUP
    _compile(one_chip,
             lambda q, s, z: int8_dequantize(q, s, z, (n // 64, 64)),
             ((r, GROUP), jnp.int8), ((r, 1), jnp.float32),
             ((r, 1), jnp.float32))


def test_int8_roundtrip_compiles_at_vgg_cohort(one_chip):
    from repro.kernels.comm_fused import fused_int8_roundtrip
    n = _vgg_cut_values()
    _compile(one_chip, fused_int8_roundtrip,
             ((D_COHORT, n), jnp.float32), ((D_COHORT, n), jnp.float32))


def test_sparse_combine_compiles_at_vgg_cohort(one_chip):
    from repro.kernels.comm_fused import fused_sparse_roundtrip
    # the VGG-16 cut, and a width that leaves a ragged last column tile
    for n in (_vgg_cut_values(), _vgg_cut_values() - 1000):
        k = n // 10             # --topk-frac default 0.1
        _compile(one_chip,
                 lambda x, r, idx: fused_sparse_roundtrip(x, r, k=k,
                                                          indices=idx),
                 ((D_COHORT, n), jnp.float32),
                 ((D_COHORT, n), jnp.float32), ((D_COHORT, k), jnp.int32))


def test_flash_attention_compiles_at_internlm2(one_chip):
    from repro.kernels.flash_attention import flash_attention
    cfg = get_config("internlm2-1.8b")
    S, H, K, Dh = 2048, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    _compile(one_chip, flash_attention,
             ((1, S, H, Dh), jnp.bfloat16), ((1, S, K, Dh), jnp.bfloat16),
             ((1, S, K, Dh), jnp.bfloat16))


def test_moe_gmm_compiles_at_deepseek_v2_lite(one_chip):
    from repro.kernels.moe_gmm import expert_ffn
    cfg = get_config("deepseek-v2-lite-16b")
    E, d, F = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    C = int(1.25 * cfg.top_k * 4096 / E)      # moe.py capacity, 4k tokens
    _compile(one_chip,
             lambda x, wg, wu, wd: expert_ffn(
                 {"w_gate": wg, "w_up": wu, "w_down": wd}, x, cfg.act),
             ((E, C, d), jnp.bfloat16), ((E, d, F), jnp.bfloat16),
             ((E, d, F), jnp.bfloat16), ((E, F, d), jnp.bfloat16))


def test_ssd_scan_compiles_at_zamba2(one_chip):
    from repro.kernels.ssd_scan import ssd_scan
    cfg = get_config("zamba2-1.2b")
    S, p, n = 2048, cfg.ssm_head_dim, cfg.ssm_state
    h = cfg.ssm_expand * cfg.d_model // p
    _compile(one_chip,
             lambda x, dt, A, B, C: ssd_scan(x, dt, A, B, C,
                                             chunk=cfg.ssm_chunk),
             ((1, S, h, p), jnp.bfloat16), ((1, S, h), jnp.float32),
             ((h,), jnp.float32), ((1, S, n), jnp.bfloat16),
             ((1, S, n), jnp.bfloat16))
