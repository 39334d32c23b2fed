"""Non-IID client partitioning: Dirichlet label skew (Hsu et al. 2019),
exactly the paper's protocol: per-class proportions ~ Dir(alpha) across
clients; 70/15/15 train/val/test split per client. And LEAF's natural
partition of FEMNIST, one writer per client (Caldas et al. 2018), at
the published writer sizes."""
from __future__ import annotations

import numpy as np


def dirichlet_partition(labels: np.ndarray, n_clients: int, alpha: float,
                        seed: int = 0, min_size: int = 12):
    """Returns list of index arrays, one per client."""
    rng = np.random.default_rng(seed)
    n_classes = int(labels.max()) + 1
    while True:
        idx_by_client = [[] for _ in range(n_clients)]
        for c in range(n_classes):
            idx_c = np.where(labels == c)[0]
            rng.shuffle(idx_c)
            props = rng.dirichlet([alpha] * n_clients)
            cuts = (np.cumsum(props) * len(idx_c)).astype(int)[:-1]
            for client, part in enumerate(np.split(idx_c, cuts)):
                idx_by_client[client].extend(part.tolist())
        sizes = [len(ix) for ix in idx_by_client]
        if min(sizes) >= min_size:
            break
        seed += 1
        rng = np.random.default_rng(seed)
    return [np.array(sorted(ix), dtype=np.int64) for ix in idx_by_client]


# LEAF FEMNIST (Caldas et al., arXiv:1812.01097, Table 1): 3,550 writers,
# 62 classes, samples per writer mean and standard deviation
FEMNIST_WRITERS = 3550
FEMNIST_SAMPLES_PER_WRITER = (226.83, 88.94)


def writers_partition(n_writers: int, n_classes: int, mean: float,
                      sd: float, seed: int = 0, min_size: int = 12):
    """One writer per client: writer sizes ~ N(mean, sd), rounded and
    held at `min_size` or more, labels uniform over the classes (LEAF
    publishes no per-class counts; FEMNIST's own classes are imbalanced,
    digits far more frequent than letters). Returns (labels, one
    contiguous index array per writer)."""
    rng = np.random.default_rng(seed)
    sizes = np.maximum(np.rint(rng.normal(mean, sd, n_writers)),
                       min_size).astype(np.int64)
    labels = rng.integers(0, n_classes, int(sizes.sum())).astype(np.int32)
    ends = np.cumsum(sizes)
    return labels, [np.arange(e - n, e) for n, e in zip(sizes, ends)]


def split_train_val_test(idx: np.ndarray, seed: int = 0,
                         fracs=(0.7, 0.15, 0.15)):
    rng = np.random.default_rng(seed)
    idx = idx.copy()
    rng.shuffle(idx)
    n = len(idx)
    n_tr = int(fracs[0] * n)
    n_va = int(fracs[1] * n)
    return idx[:n_tr], idx[n_tr:n_tr + n_va], idx[n_tr + n_va:]


def partition_stats(labels: np.ndarray, parts) -> dict:
    """Client x class count matrix (paper Fig. 4)."""
    n_classes = int(labels.max()) + 1
    mat = np.zeros((len(parts), n_classes), np.int64)
    for i, ix in enumerate(parts):
        for c, n in zip(*np.unique(labels[ix], return_counts=True)):
            mat[i, c] = n
    return {"counts": mat, "sizes": mat.sum(1)}
