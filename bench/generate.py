"""The benchmark's own data: images and their split over participants,
drawn from seeds.

Copied from the program (``repro.data.synthetic.make_classification`` and
``repro.data.partition.dirichlet_partition``) so that no change to the
program moves the yardstick's data.  Two seeds enter:

* the configuration's ``layout_seed`` fixes the labels and the Dirichlet
  split, so every run has the same shard sizes and therefore the same
  cluster layout (Procedure 2 places participants by their data size);
* ``--seed`` draws the class prototypes, the noise and the gains (the
  pixels).
"""
from __future__ import annotations

import numpy as np


def make_images(spec: dict, n: int, seed: int, *, offset: int = 0):
    """(x, y): ``n`` images of ``spec["shape"]`` from class prototypes plus
    noise, scaled by a per-image gain.  ``offset`` skips that many labels of
    the layout stream, so a held-out set does not repeat the training
    labels."""
    shape, classes = tuple(spec["shape"]), int(spec["classes"])
    lay = np.random.default_rng(spec["layout_seed"])
    y = lay.integers(0, classes, offset + n).astype(np.int32)[offset:]
    protos = np.random.default_rng([seed, 0]).standard_normal(
        (classes,) + shape, dtype=np.float32)
    rng = np.random.default_rng([seed, 1, offset])
    x = rng.standard_normal((n,) + shape, dtype=np.float32)
    x *= np.float32(spec["noise"])
    x += protos[y]
    lo, hi = spec["gain_range"]
    x *= rng.uniform(lo, hi, (n, 1, 1, 1)).astype(np.float32)
    return x, y


def dirichlet_partition(labels: np.ndarray, n_clients: int, alpha: float,
                        seed: int, min_per_client: int = 8) -> list:
    """Label-skew split: per class, Dirichlet(alpha) shares over clients;
    clients left with fewer than ``min_per_client`` items are topped up
    from a shuffled pool.  Returns sorted index arrays, one per client."""
    rng = np.random.default_rng(seed)
    shares = [[] for _ in range(n_clients)]
    for c in np.unique(labels):
        idx = np.where(labels == c)[0]
        rng.shuffle(idx)
        p = rng.dirichlet(np.full(n_clients, alpha))
        cuts = (np.cumsum(p) * len(idx)).astype(int)[:-1]
        for cl, part in enumerate(np.split(idx, cuts)):
            shares[cl].append(part)
    out = [np.sort(np.concatenate(s)) for s in shares]
    pool = np.concatenate(out)
    rng.shuffle(pool)
    for i, o in enumerate(out):
        if len(o) < min_per_client:
            out[i] = np.sort(np.concatenate([o, pool[:min_per_client - len(o)]]))
    return out


def federated_data(cfg: dict, seed: int):
    """Per-participant shards ``[{"x", "y"}]`` and a 256-image held-out set
    for the configuration ``cfg`` under ``seed``."""
    ds, fed = cfg["dataset"], cfg["participants"]
    x, y = make_images(ds, ds["train"], seed)
    idx = dirichlet_partition(y, len(fed["table_iii"]),
                              fed["dirichlet_alpha"], ds["layout_seed"],
                              fed["min_per_client"])
    shards = [{"x": x[p], "y": y[p]} for p in idx]
    xt, yt = make_images(ds, 256, seed, offset=ds["train"])
    return shards, {"x": xt, "y": yt}
