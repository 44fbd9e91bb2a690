"""Sharding/dry-run machinery tests at small scale.

The production 512-device dry-run can't run inside pytest (device count
locks at first jax init — see launch/dryrun.py), so here we:
  - verify param PartitionSpecs respect divisibility and single-claim
  - lower the fused train step on a small in-process mesh via subprocess
  - unit-test the HLO collective parser on synthetic HLO text
"""
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models.params import is_def, param_specs
from repro.models.sharding import mesh_rules

# training-heavy module: the quick loop skips it (-m "not slow"; see pytest.ini)
pytestmark = pytest.mark.slow
from repro.models.transformer import model_defs
from repro.utils.hlo import collective_bytes


class FakeMesh:
    axis_names = ("data", "model")

    class _D:
        shape = (16, 16)
        size = 256
    devices = _D()


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "kimi-k2-1t-a32b",
                                  "mamba2-2.7b", "internvl2-1b",
                                  "gemma3-27b"])
def test_param_specs_divisible(arch):
    cfg = get_config(arch)
    defs = model_defs(cfg)
    specs = param_specs(defs, mesh_rules(cfg, FakeMesh()))
    flat_defs = jax.tree.leaves(defs, is_leaf=is_def)
    flat_specs = jax.tree.leaves(specs,
                                 is_leaf=lambda x: isinstance(x, P))
    assert len(flat_defs) == len(flat_specs)
    for d, s in zip(flat_defs, flat_specs):
        used = [ax for ax in s if ax is not None]
        assert len(used) == len(set(used)), (d, s)   # single-claim
        for dim, ax in zip(d.shape, s):
            if ax == "model":
                assert dim % 16 == 0, (d, s)
            if ax == "data":
                assert dim % 16 == 0, (d, s)


def test_kimi_experts_sharded_two_axes():
    """The 1T MoE must shard experts over `model` AND expert ff over
    `data` (fsdp_ff) or it cannot fit 256 chips."""
    cfg = get_config("kimi-k2-1t-a32b")
    defs = model_defs(cfg)
    specs = param_specs(defs, mesh_rules(cfg, FakeMesh()))
    moe_spec = specs["blocks"][1]["ffn"]["w_gate"]
    assert moe_spec[0] == "model" and "data" in tuple(moe_spec), moe_spec


def test_collective_parser():
    hlo = textwrap.dedent("""
      %ar = bf16[128,1024]{1,0} all-reduce(%x), replica_groups={}
      %ag.1 = f32[64,64]{1,0} all-gather(%y), dimensions={0}
      %t = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) all-to-all(%a, %b)
      %cp = u32[16]{0} collective-permute(%z)
      %not_a_coll = f32[2,2]{1,0} add(%p, %q)
    """)
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 128 * 1024 * 2
    assert out["all-gather"] == 64 * 64 * 4
    assert out["all-to-all"] == 2 * 8 * 8 * 2
    assert out["collective-permute"] == 16 * 4
    assert out["_total"] == sum(out[k] for k in
                                ("all-reduce", "all-gather", "all-to-all",
                                 "collective-permute", "reduce-scatter"))


def test_collective_parser_ignores_async_done():
    hlo = ("%s = bf16[64]{0} all-gather-start(%x)\n"
           "%d = bf16[64]{0} all-gather-done(%s)\n")
    out = collective_bytes(hlo)
    assert out["all-gather"] == 64 * 2      # start counted once


DRYRUN_SMALL = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_config, make_reduced
from repro.core.round_step import make_s2fl_train_step, train_step_shardings
from repro.launch.mesh import make_mesh
from repro.launch.steps import train_inputs
from repro.models.transformer import abstract_model


cfg = make_reduced(get_config("{arch}"))
mesh = make_mesh((4, 2), ("data", "model"))
step = make_s2fl_train_step(cfg, 1, 2, 0.01, dp_axes=("data",))
batch = train_inputs(cfg, batch=8, seq=32)
in_sh, out_sh = train_step_shardings(cfg, mesh, batch)
with mesh:
    c = jax.jit(step, in_shardings=in_sh,
                out_shardings=out_sh).lower(abstract_model(cfg),
                                            batch).compile()
cost = c.cost_analysis()
assert cost["flops"] > 0
print("OK", cost["flops"])
"""


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "zamba2-1.2b"])
def test_fused_step_lowers_on_small_mesh(arch):
    """Real lower+compile of the fused S²FL step on an 8-device host mesh
    (subprocess so the device count doesn't leak into this session)."""
    r = subprocess.run(
        [sys.executable, "-c", DRYRUN_SMALL.format(arch=arch)],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root",
             # host-mesh lowering needs the CPU platform; skipping the
             # TPU probe also avoids a 60s metadata timeout on CI
             "JAX_PLATFORMS": "cpu"},
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
