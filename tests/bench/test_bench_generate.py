"""The benchmark's own data: the same seed gives the same data, and the
layout seed fixes the shard sizes."""
import json
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def config(name="fedrac-cnn-cifar10", train=2000):
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    c["dataset"]["shape"] = [8, 8, 3]
    c["dataset"]["train"] = train
    return c


def test_same_seed_same_data_layout_fixed():
    from bench import generate
    a, ta = generate.federated_data(config(), 7)
    b, _ = generate.federated_data(config(), 7)
    c, _ = generate.federated_data(config(), 2**31 + 5)
    assert len(a) == 40
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(x["x"], y["x"])
        # another seed: the same labels and shard sizes, other pixels
        np.testing.assert_array_equal(x["y"], z["y"])
        assert not np.array_equal(x["x"], z["x"])
    assert ta["x"].shape == (256, 8, 8, 3)


def test_partition_covers_every_item_and_tops_up():
    from bench import generate
    y = np.random.default_rng(0).integers(0, 10, 1000)
    parts = generate.dirichlet_partition(y, 40, 0.1, seed=3,
                                         min_per_client=8)
    assert min(len(p) for p in parts) >= 8
    assert set(np.concatenate(parts)) == set(range(1000))
