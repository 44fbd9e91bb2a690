"""Algorithm 1 — three-portion model aggregation.

Because the split slides per device, a given layer (segment) may have been
trained on some devices' clients and, for the others, inside their group's
server-side copy. For every segment of the full model W:

    W[seg] = sum_i |D_i| * source_i[seg]  /  sum_i |D_i|

where source_i = client params of device i if the segment lies in its
client portion, else the server copy of device i's group — exactly lines
3–17 of Algorithm 1 (weights are data sizes |D_i|).

The sources are picked on the host (array references, no device op); the
weighted sums of every segment then run as one compiled program per
commit, keyed by the number of devices and the tree structure. The
scales are a traced argument, so new data sizes never recompile.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.models.api import SplitModel
from repro.utils.tree import get_subtree, set_subtree


@dataclasses.dataclass
class ClientState:
    cid: int
    params: dict                   # trained client-side params (full tree)
    split: int
    data_size: float
    group: int


def _leaf_sum(scales, *xs):
    """sum_i scales[i] * xs[i], in device order, in each leaf's dtype."""
    acc = xs[0] * scales[0].astype(xs[0].dtype)
    for i, x in enumerate(xs[1:], 1):
        acc = acc + x * scales[i].astype(x.dtype)
    return acc


@jax.jit
def weighted_sums(sources, scales):
    """sources[g][i]: device i's pytree for output g; scales[i] its weight.
    Returns one pytree per g. Nothing is donated: the inputs are held
    client and server states the caller may still read."""
    return [jax.tree.map(lambda *xs: _leaf_sum(scales, *xs), *trees)
            for trees in sources]


def _scales(weights) -> np.ndarray:
    total = sum(weights)
    return np.asarray([w / total for w in weights], np.float32)


def aggregate(model: SplitModel, clients: list, server_copies: dict) -> dict:
    """clients: list[ClientState]; server_copies: {group_id: params}.
    Returns the aggregated full model W."""
    assert clients, "no clients to aggregate"
    segments = model.segments()
    sources = [[get_subtree(c.params if name in model.client_segments(c.split)
                            else server_copies[c.group], path)
                for c in clients]
               for name, path in segments]
    sums = weighted_sums(sources, _scales([c.data_size for c in clients]))
    out = clients[0].params        # template for reassembly
    for (_, path), sub in zip(segments, sums):
        out = set_subtree(out, path, sub)
    return out


def fedavg_aggregate(params_list, weights):
    """Plain FedAvg weighted average (baseline)."""
    return weighted_sums([params_list], _scales(weights))[0]
