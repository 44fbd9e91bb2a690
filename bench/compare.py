"""The comparison that decides ``correct`` for a training cell.

The program's first three steps, taken in set-up through the window's own
call, are set beside the plain reference's three steps from the same
weights and inputs. Compared, each against its limit
(``bench/limits/<cell>.json``):

- ``loss1``..``loss3``: each step's loss, as the gap relative to the
  reference's;
- ``grad1``: the first gradient as the optimizer applied it, read from the
  parameters after one step, ``||w1 - w0||`` leaf by leaf;
- ``delta3``: the parameters' change after three steps, ``||w3 - w0||``
  leaf by leaf.

Both leaf-wise numbers take the worst leaf: the gap between the program's
norm and the reference's, over the larger of the reference's norm of that
leaf and of the median leaf. Leaves whose first reference change is under
a thousandth of the median leaf's are left out: there the change is
round-off alone.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def seed_key(seed: int):
    """A JAX key from any whole seed (JAX keeps 32 bits of an int; the
    rest is folded in, so seeds past 2**32 stay distinct)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def leaf_gap(prog, ref, counted) -> float:
    """Worst counted leaf of |prog - ref| / max(ref, median ref)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    idx = np.flatnonzero(counted)
    med = float(np.median(ref[idx]))
    return float(max(abs(prog[i] - ref[i]) / max(ref[i], med)
                     for i in idx))


def counted_leaves(ref_first) -> np.ndarray:
    ref_first = np.asarray(ref_first, np.float64)
    return ref_first >= 1e-3 * float(np.median(ref_first))


def training_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref``: {'losses': [l1, l2, l3], 'n1': leaf norms of
    w1 - w0, 'n3': leaf norms of w3 - w0}. Returns {name: number}."""
    out = {f"loss{i + 1}": abs(p - r) / abs(r)
           for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]))}
    keep = counted_leaves(ref["n1"])
    out["grad1"] = leaf_gap(prog["n1"], ref["n1"], keep)
    out["delta3"] = leaf_gap(prog["n3"], ref["n3"], keep)
    if not all(math.isfinite(v) for v in prog["losses"]):
        out = {k: float("inf") for k in out}
    return out


def limits_of(cell: str) -> dict:
    with open(os.path.join(HERE, "limits", cell + ".json")) as f:
        return json.load(f)["limits"]


def checks(numbers: dict, limits: dict) -> list:
    """The compared numbers beside their limits; a number with no limit
    (see the limits file for why) is printed by the caller, not held."""
    return [{"name": k, "value": v, "limit": limits[k]}
            for k, v in numbers.items() if k in limits]
