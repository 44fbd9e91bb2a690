"""Production mesh construction. Functions (not module constants) so
importing never touches jax device state.

Every mesh here has ``Auto`` axes: the sharding rules and the fused
round step place arrays with ``NamedSharding`` and
``with_sharding_constraint``, which JAX accepts only on Auto axes
(``jax.make_mesh`` defaults to Explicit axes since JAX 0.7)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types, over ``devices`` (default:
    all of this process's devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:data * model])
