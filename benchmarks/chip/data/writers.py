"""The validation labels of LEAF's FEMNIST partition, one writer per
client, at the published writer sizes: a copy of the program's
generator (src/repro/data/partition.py `writers_partition`), draw for
draw, with the 70/15/15 per-client split of `images.py`.
"""
from __future__ import annotations

import numpy as np

from chip.data.images import split_train_val_test


def writers_partition(n_writers: int, n_classes: int, mean: float,
                      sd: float, seed: int, min_size: int = 12):
    """(labels, one contiguous index array per writer): writer sizes
    ~ N(mean, sd), rounded, at least `min_size`; labels uniform (an
    assumption: FEMNIST's classes are imbalanced)."""
    rng = np.random.default_rng(seed)
    sizes = np.maximum(np.rint(rng.normal(mean, sd, n_writers)),
                       min_size).astype(np.int64)
    labels = rng.integers(0, n_classes, int(sizes.sum())).astype(np.int32)
    ends = np.cumsum(sizes)
    return labels, [np.arange(e - n, e) for n, e in zip(sizes, ends)]


def writer_val_labels(cfg: dict) -> list:
    """Each writer's validation labels, as the program's prediction
    world makes them: partition seed = data seed, split seed = data
    seed + 1."""
    seed = cfg["data_seed"]
    labels, parts = writers_partition(cfg["n_clients"], cfg["n_classes"],
                                      *cfg["samples_per_writer"], seed)
    return [labels[split_train_val_test(ix, seed + 1, tuple(cfg["split"]))[1]]
            for ix in parts]
