"""FL substrate tests: Dirichlet partition properties, topology
connectivity, async gossip convergence, baseline smoke runs."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data import dirichlet_partition, make_synthetic_images, split_train_val_test
from repro.data.partition import partition_stats
from repro.fl.scheduler import AsyncConfig, simulate_async
from repro.fl.topology import make_topology


@settings(max_examples=10, deadline=None)
@given(st.integers(4, 12), st.sampled_from([0.1, 0.3, 0.5]), st.integers(0, 100))
def test_dirichlet_partition_conserves_samples(n_clients, alpha, seed):
    labels = np.random.default_rng(seed).integers(0, 10, 2000)
    parts = dirichlet_partition(labels, n_clients, alpha, seed=seed)
    allidx = np.concatenate(parts)
    assert len(allidx) == len(labels)
    assert len(np.unique(allidx)) == len(labels)  # exact partition


def test_dirichlet_alpha_controls_skew():
    labels = np.random.default_rng(0).integers(0, 10, 20000)
    def skew(alpha):
        parts = dirichlet_partition(labels, 10, alpha, seed=0)
        counts = partition_stats(labels, parts)["counts"]
        p = counts / np.maximum(counts.sum(1, keepdims=True), 1)
        ent = -(p * np.log(p + 1e-12)).sum(1)
        return ent.mean()
    assert skew(0.1) < skew(0.5) < skew(100.0)  # lower alpha = lower entropy


def test_split_fractions():
    idx = np.arange(1000)
    tr, va, te = split_train_val_test(idx, seed=0)
    assert len(tr) == 700 and len(va) == 150
    assert len(set(tr) | set(va) | set(te)) == 1000


@pytest.mark.parametrize("name", ["full", "ring", "random", "small_world"])
def test_topology_connected_and_symmetric(name):
    n = 12
    nb = make_topology(name, n, k=3, seed=0)
    for i in range(n):
        for j in nb[i]:
            assert i in nb[j], "asymmetric edge"
    # connectivity by BFS
    seen, frontier = {0}, [0]
    while frontier:
        cur = frontier.pop()
        for j in nb[cur]:
            if j not in seen:
                seen.add(j)
                frontier.append(j)
    assert len(seen) == n


@pytest.mark.parametrize("topo", ["full", "ring", "random", "small_world"])
def test_async_gossip_every_model_reaches_every_client(topo):
    """On a connected graph with relay-on-receive = none (single hop), only
    full topology delivers everything directly; ring/random still record
    monotone bench growth. Full graph must converge completely."""
    cfg = AsyncConfig(n_clients=6, models_per_client=2, seed=0)
    nb = make_topology(topo, 6, k=3, seed=0)
    trace = simulate_async(cfg, nb, train_cost=lambda c, m: 1.0 + 0.1 * m)
    # bench sizes monotone
    for c, series in trace.bench_sizes.items():
        sizes = [s for _, s in series]
        assert sizes == sorted(sizes)
    if topo == "full":
        final = {c: series[-1][1] for c, series in trace.bench_sizes.items()}
        assert all(v == 12 for v in final.values())


def test_async_ordering_is_causal():
    cfg = AsyncConfig(n_clients=4, models_per_client=1, seed=1)
    nb = make_topology("full", 4)
    trace = simulate_async(cfg, nb, train_cost=lambda c, m: 1.0)
    times = [t for t, *_ in trace.events]
    assert times == sorted(times)
    # a model is never received before it was trained
    trained_at = {}
    for t, kind, c, payload in trace.events:
        if kind == "trained":
            trained_at[payload] = t
        elif kind == "recv":
            assert t >= trained_at[payload]


def test_topology_k_too_large_raises():
    for name in ("random", "small_world"):
        with pytest.raises(ValueError, match="k < n"):
            make_topology(name, 4, k=4)


def test_baselines_two_round_smoke():
    from repro.fl.baselines import BASELINES, FLConfig
    from repro.fl.client import ClientData
    ds = make_synthetic_images(600, 6, size=8, seed=0)
    parts = dirichlet_partition(ds.y, 3, 0.5, seed=0)
    datasets = []
    for ix in parts:
        tr, va, te = split_train_val_test(ix, seed=1)
        datasets.append(ClientData(ds.x[tr], ds.y[tr], ds.x[va], ds.y[va],
                                   ds.x[te], ds.y[te]))
    fl = FLConfig(rounds=2, local_steps=1, families=("cnn4", "vgg"), width=8)
    for name, fn in BASELINES.items():
        acc = fn(datasets, 6, fl)
        assert acc.shape == (3,)
        assert np.isfinite(acc).all(), name
