"""Jit'd public wrapper: expert FFN on capacity-bucketed inputs.
Compiled on TPU, interpreted on CPU (``repro.kernels.backend``)."""
from __future__ import annotations

from repro.kernels.backend import run_kernel
from repro.kernels.moe_gmm.kernel import moe_gmm


def expert_ffn(p, exp_in, act: str = "silu"):
    """p: moe param dict with w_gate/w_up/w_down (E, ...); exp_in (E, C, d)."""
    d = exp_in.shape[-1]
    block_f = 128 if d > 4096 else 256        # VMEM budget, see kernel.py
    return run_kernel(moe_gmm, exp_in, p["w_gate"], p["w_up"], p["w_down"],
                      act=act, block_f=block_f)
