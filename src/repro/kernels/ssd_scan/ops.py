"""Jit'd public wrapper for the SSD scan kernel. Compiled on TPU,
interpreted on CPU (``repro.kernels.backend``)."""
from __future__ import annotations

from repro.kernels.backend import run_kernel
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, initial_state=None):
    return run_kernel(ssd_scan_pallas, x, dt, A, B, C, initial_state,
                      chunk=chunk)
