"""Seeded inputs of the federated CNN cells: CIFAR-shaped images and their
Dirichlet partition over devices.

Copies of the program's ``data/synthetic.make_image_dataset`` and
``data/partition.dirichlet_partition``, kept here so that the inputs stay
the same whatever the program does to its own. One change: the labels,
and so the partition and every device's data size, come from the
federation's own fixed seed, and the run's seed draws the images (class
prototypes and noise). So every run seed trains the same federation, with
the same shapes in the same rounds, on other values.
"""
from __future__ import annotations

import numpy as np


def make_images(n: int, *, n_classes: int, image_size: int, channels: int,
                noise: float, label_seed: int, value_seed: int) -> dict:
    """{'x': (n, H, W, C) f32, 'y': (n,) i32}: smooth random class
    prototypes (low-frequency fields) plus Gaussian noise per sample."""
    y = np.random.default_rng(label_seed).integers(0, n_classes, size=n)
    rng = np.random.default_rng(value_seed)
    freq = 4
    base = rng.normal(size=(n_classes, freq, freq, channels))
    protos = np.stack([
        np.kron(base[c], np.ones((image_size // freq, image_size // freq, 1)))
        for c in range(n_classes)])
    protos = protos / np.abs(protos).max()
    x = protos[y] + noise * rng.standard_normal(
        size=(n, image_size, image_size, channels), dtype=np.float32)
    return {"x": x.astype(np.float32), "y": y.astype(np.int32)}


def dirichlet_partition(labels, n_clients: int, alpha: float,
                        seed: int = 0, min_per_client: int = 2) -> list:
    """One index array per client: each class's samples split by
    Dirichlet(alpha) proportions, then every client topped up to
    ``min_per_client`` from the largest."""
    rng = np.random.default_rng(seed)
    labels = np.asarray(labels)
    n_classes = int(labels.max()) + 1
    client_idx = [[] for _ in range(n_clients)]
    for c in range(n_classes):
        idx = np.flatnonzero(labels == c)
        rng.shuffle(idx)
        props = rng.dirichlet([alpha] * n_clients)
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for cid, part in enumerate(np.split(idx, cuts)):
            client_idx[cid].extend(part.tolist())
    for cid in range(n_clients):
        while len(client_idx[cid]) < min_per_client:
            donor = int(np.argmax([len(ci) for ci in client_idx]))
            client_idx[cid].append(client_idx[donor].pop())
    return [np.asarray(sorted(ci), dtype=np.int64) for ci in client_idx]


def federation(c: dict, t: dict, seed: int) -> dict:
    """{device id: {'x', 'y'}} for configuration ``c`` under traffic ``t``."""
    data = make_images(t["n_train"], n_classes=c["n_classes"],
                       image_size=c["image_size"],
                       channels=c["in_channels"], noise=t["noise"],
                       label_seed=t["federation_seed"], value_seed=seed)
    parts = dirichlet_partition(data["y"], t["clients"], t["alpha"],
                                seed=t["federation_seed"])
    return {cid: {k: v[p] for k, v in data.items()}
            for cid, p in enumerate(parts)}
