"""Operations and bytes of the ``comm_fused`` Pallas kernels
(``kernels/comm_fused/kernel.py``), from the shapes of one call.

``_int8_roundtrip_kernel`` reads an (R, G) block of f32 group rows and
writes the dequantized rows: per value a min and a max, a divide, an add,
a round, two clips, a subtract and a multiply (9 operations).
``_sparse_combine_kernel`` reads y and the survivor mask, (D, N) f32 each,
and writes the delivered tensor and the residual: two multiplies and a
subtract per value.
"""
from __future__ import annotations


def int8_roundtrip(rows: int, group: int, itemsize: int = 4) -> dict:
    n = rows * group
    return {"bytes": 2.0 * n * itemsize, "ops": 9.0 * n}


def sparse_combine(d: int, n: int, itemsize: int = 4) -> dict:
    return {"bytes": 4.0 * d * n * itemsize, "ops": 3.0 * d * n}


def least_seconds(cost: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of bytes over HBM
    bandwidth and operations over the bf16 peak."""
    return max(cost["bytes"] / peaks["hbm_bytes_per_s"],
               cost["ops"] / peaks["bf16_flops"])
