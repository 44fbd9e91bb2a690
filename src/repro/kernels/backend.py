"""The one rule by which every Pallas kernel here picks its backend.

The kernels are written for the TPU: they run compiled there, and under
the Pallas interpreter on the CPU, or on a jnp oracle where the caller
names one (the comm codecs do: interpreting their kernels in the
per-step training path would be the slowest choice). The choice is made
by ``jax.lax.platform_dependent`` when the surrounding program is
lowered, from the platform it is compiled for, not from the process's
default backend. So a program placed on the host CPU of a TPU machine
(``jax.default_device(jax.devices("cpu")[0])``) takes the CPU branch, and
a program compiled for a described TPU takes the compiled kernel. A
platform with neither branch is an error, never a silent fallback.
"""
from __future__ import annotations

import functools

import jax


def run_kernel(kernel, *args, cpu=None, **static):
    """``kernel(*args, interpret=False, **static)`` on TPU; on CPU
    ``cpu(*args)`` when an oracle is given, else the same kernel under
    ``interpret=True``. ``static`` holds the kernel's hashable keyword
    arguments (block sizes, dtypes, flags); ``args`` are arrays."""
    tpu_fn = functools.partial(kernel, interpret=False, **static)
    cpu_fn = cpu or functools.partial(kernel, interpret=True, **static)
    return jax.lax.platform_dependent(*args, cpu=cpu_fn, tpu=tpu_fn)
