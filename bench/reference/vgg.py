"""Plain float32 reference of a VGG-style CNN (arXiv 1409.1556, CIFAR
variant) and of S²FL rounds over it (the S²FL paper, Algorithm 2 at
E = 1 with Algorithm-1 aggregation).

The network: per stage, 3x3 'same' convolutions without bias, each
followed by batch normalisation over the batch's own statistics (no
running averages, as federated training keeps them) and a ReLU; a 2x2 max
pool closes each stage; the head averages the map and applies one dense
layer. The loss is the batch's mean cross-entropy. It imports nothing of
the program.

One round, for each balance group: every member runs the network's first
``split`` units on its own batch from the round's global weights and
sends the features through the uplink codec; the Main Server sums the
members' losses of the remaining units and the head, evaluated with its
copy of the global weights, takes one SGD step on that copy, and returns
each member the gradient of its features through the downlink codec;
each member takes one SGD step on its client units with it. When a
group's work is committed (at once in a synchronous round, up to the
staleness cap later in a semi-asynchronous one), Algorithm 1 makes every
parameter segment (unit or head) the data-size-weighted mean, over the
committed devices, of the device's own copy where the segment lies in its
client part, else its group's server copy. The round's loss is the
summed group losses over the number of devices trained.

``keep`` < 1 keeps only the first share of every batch: a planted fault.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def layout(c: dict) -> dict:
    units, cin = [], c["in_channels"]
    for cout, n in c["stages"]:
        for _ in range(n):
            units.append({"conv": {"w": (3, 3, cin, cout)},
                          "bn": {"scale": (cout,), "bias": (cout,)}})
            cin = cout
    return {"units": units,
            "head": {"w": (cin, c["n_classes"]), "b": (c["n_classes"],)}}


def make_weights(key, c: dict):
    """Convolutions N(0, 2/fan_in), batch-norm scale 1 and bias 0, head
    N(0, 1/fan_in) and bias 0. Call under ``jax.jit``."""
    lay = layout(c)
    units = []
    for i, u in enumerate(lay["units"]):
        s = u["conv"]["w"]
        std = math.sqrt(2.0 / (s[0] * s[1] * s[2]))
        units.append({"conv": {"w": std * jax.random.normal(
            jax.random.fold_in(key, i), s, jnp.float32)},
            "bn": {"scale": jnp.ones(u["bn"]["scale"], jnp.float32),
                   "bias": jnp.zeros(u["bn"]["bias"], jnp.float32)}})
    hw = lay["head"]["w"]
    head = {"w": jax.random.normal(jax.random.fold_in(key, len(units)), hw,
                                   jnp.float32) / math.sqrt(hw[0]),
            "b": jnp.zeros(lay["head"]["b"], jnp.float32)}
    return {"units": units, "head": head}


def unit_is_last(c: dict) -> list:
    out = []
    for _, n in c["stages"]:
        out += [False] * (n - 1) + [True]
    return out


def apply_units(params, x, lo: int, hi: int, c: dict):
    last = unit_is_last(c)
    for i in range(lo, hi):
        p = params["units"][i]
        x = jax.lax.conv_general_dilated(
            x, p["conv"]["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
        x = (x - mean) / jnp.sqrt(var + 1e-5) * p["bn"]["scale"] \
            + p["bn"]["bias"]
        x = jax.nn.relu(x)
        if last[i]:
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    return x


def server_loss(params, h, y, split: int, c: dict):
    n = len(unit_is_last(c))
    h = apply_units(params, h, split, n, c)
    logits = jnp.mean(h, axis=(1, 2)) @ params["head"]["w"] \
        + params["head"]["b"]
    picked = jnp.take_along_axis(logits, y[:, None], 1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def _pieces(c_json: str, lr: float):
    """Jitted pieces of a round, one program per signature: a member's
    forward, a group's server loss with its gradients, and a member's
    update from the gradient of its features."""
    import json
    c = json.loads(c_json)

    @functools.partial(jax.jit, static_argnums=2)
    def fwd(w, x, s):
        return apply_units(w, x, 0, s, c)

    @functools.partial(jax.jit, static_argnums=3)
    def server(w, hs, ys, splits):
        def loss(sp, hs):
            return sum(server_loss(sp, h, y, s, c)
                       for h, y, s in zip(hs, ys, splits))
        val, (g_s, g_h) = jax.value_and_grad(loss, argnums=(0, 1))(w, hs)
        return val, jax.tree.map(lambda a, g: a - lr * g, w, g_s), g_h

    @functools.partial(jax.jit, static_argnums=3)
    def client(w, x, gh, s):
        _, vjp = jax.vjp(lambda p: apply_units(p, x, 0, s, c), w)
        (g,) = vjp(gh)
        return jax.tree.map(lambda a, gc: a - lr * gc, w, g)

    return fwd, server, client


_PIECES = {}


def int8_roundtrip(x, group: int = 256):
    """Affine int8 over groups of ``group`` consecutive values of the
    flattened tensor (the tail group padded with its last value): per
    group min and max map to -127 and 127, values round to the nearest
    step and come back."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    g = min(group, n)
    rows = jnp.pad(flat, (0, (-n) % g), mode="edge").reshape(-1, g)
    mn = jnp.min(rows, 1, keepdims=True)
    mx = jnp.max(rows, 1, keepdims=True)
    scale = jnp.maximum((mx - mn) / 254.0, 1e-12)
    zp = -127.0 - mn / scale
    q = jnp.clip(jnp.round(rows / scale + zp), -127.0, 127.0)
    return (scale * (q - zp)).reshape(-1)[:n].reshape(x.shape)


def topk_roundtrip(x, frac: float):
    """The ceil(frac * n) entries of largest magnitude kept, the rest 0."""
    flat = x.reshape(-1)
    k = max(1, math.ceil(frac * flat.shape[0]))
    idx = jax.lax.top_k(jnp.abs(flat), k)[1]
    return jnp.zeros_like(flat).at[idx].set(flat[idx]).reshape(x.shape)


class Link:
    """One direction of the cut-layer exchange: a codec round trip, with
    the error feedback of each device's last transfer added back before
    the next encode when on (a residual of another shape is dropped)."""

    def __init__(self, codec: str, *, frac: float = 0.1,
                 feedback: bool = False):
        fns = {"fp32": None, "int8": int8_roundtrip,
               "topk": functools.partial(topk_roundtrip, frac=frac)}
        self.fn = jax.jit(fns[codec]) if fns[codec] else None
        self.feedback = feedback and self.fn is not None
        self.residual = {}

    def __call__(self, cid, x):
        if self.fn is None:
            return x
        r = self.residual.get(cid)
        if self.feedback and r is not None and r.shape == x.shape:
            x = x + r
        y = self.fn(x)
        if self.feedback:
            self.residual[cid] = x - y
        return y


def train_groups(w, groups, c: dict, lr: float, up: Link, down: Link,
                 keep: float = 1.0) -> dict:
    """Every group of one round trained from the global weights ``w``.
    ``groups``: {gid: [(cid, split, x, y, size), ...]}. Returns {gid:
    (group loss, server copy, [(own copy, split, size), ...])}."""
    import json
    key = (json.dumps(c, sort_keys=True), lr)
    if key not in _PIECES:
        _PIECES[key] = _pieces(*key)
    fwd, server, client = _PIECES[key]
    out = {}
    for gid in sorted(groups):
        members = [(cid, s, x[:max(1, int(len(x) * keep))],
                    y[:max(1, int(len(y) * keep))], n)
                   for cid, s, x, y, n in groups[gid]]
        splits = tuple(m[1] for m in members)
        hs = [up(cid, fwd(w, x, s)) for cid, s, x, _, _ in members]
        val, sw, ghs = server(w, hs, [m[3] for m in members], splits)
        own = [(client(w, x, down(cid, gh), s), s, n)
               for (cid, s, x, _, n), gh in zip(members, ghs)]
        out[gid] = (float(val), sw, own)
    return out


def aggregate(results: list, c: dict):
    """Algorithm 1 over the committed groups' results: every segment is
    the data-size-weighted mean of each device's own copy where the
    segment lies in its client part, else its group's server copy."""
    devices = [(own, sw, s, n) for _, sw, owns in results
               for own, s, n in owns]
    n_units = len(unit_is_last(c))
    total = sum(d[3] for d in devices)

    def mean_of(pick):
        return jax.tree.map(lambda *xs: sum(
            d[3] * x for d, x in zip(devices, xs)) / total,
            *[pick(d) for d in devices])

    units = [mean_of(lambda d, i=i: (d[0] if i < d[2] else d[1])
                     ["units"][i]) for i in range(n_units)]
    return {"units": units, "head": mean_of(lambda d: d[1]["head"])}
