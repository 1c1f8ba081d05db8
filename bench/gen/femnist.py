"""Synthetic FEMNIST stand-in at the source's shapes (28×28 = 784 features,
62 classes) and the paper's shards-per-client partition.

A copy of the repository's generator (``repro.data.synthetic.
make_femnist_like`` and ``repro.data.partition.partition_spc``), kept here
so that the data the benchmark feeds and its reference reads cannot move
with the program.  Classes are Gaussian clusters on a 32-dimensional
manifold lifted through a fixed random nonlinearity."""
from __future__ import annotations

import numpy as np


def femnist_like(n_samples: int, *, num_classes: int = 62, dim: int = 784,
                 seed: int = 0, noise: float = 0.9):
    """``(x (n, dim) float32, y (n,) int32)`` drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    latent_dim = 32
    class_means = rng.normal(0, 1.5, size=(num_classes, latent_dim))
    lift = rng.normal(0, 1.0, size=(latent_dim, dim)) / np.sqrt(latent_dim)
    lift2 = rng.normal(0, 1.0, size=(latent_dim, dim)) / np.sqrt(latent_dim)
    y = rng.integers(0, num_classes, size=n_samples).astype(np.int32)
    z = class_means[y] + rng.normal(0, noise, size=(n_samples, latent_dim))
    x = np.tanh(z @ lift) + 0.5 * np.sin(z @ lift2)
    x = (x + rng.normal(0, 0.3, size=x.shape)).astype(np.float32)
    return x, y


def partition_spc(labels: np.ndarray, n_clients: int, spc: int,
                  seed: int) -> list:
    """Shards per client: sort by label, cut ``n_clients·spc`` equal
    shards, deal ``spc`` to each client.  Every client holds exactly
    ``len(labels) // (n_clients·spc) · spc`` samples."""
    rng = np.random.default_rng(seed)
    n_shards = n_clients * spc
    order = np.argsort(labels, kind="stable")
    size = len(labels) // n_shards
    shards = [order[i * size:(i + 1) * size] for i in range(n_shards)]
    perm = rng.permutation(n_shards)
    return [np.sort(np.concatenate([shards[perm[c * spc + j]]
                                    for j in range(spc)]))
            for c in range(n_clients)]


def fl_dataset(fl: dict, seed: int):
    """The population's client tables and the held-out set.

    Returns ``(x_tab (N, n_i, 784), y_tab (N, n_i), eval_x, eval_y, parts)``
    with ``parts`` the per-client sample indices into the training split.
    """
    spc = {"1spc": 1, "2spc": 2}[fl["partition"]]
    n, per = fl["n_clients"], fl["samples_per_client"]
    total = n * per
    x, y = femnist_like(total + fl["eval_size"], seed=seed)
    tx, ty = x[:total], y[:total]
    parts = partition_spc(ty, n, spc, seed)
    x_tab = np.stack([tx[p] for p in parts])
    y_tab = np.stack([ty[p] for p in parts])
    return x_tab, y_tab, x[total:], y[total:], tx, ty, parts
