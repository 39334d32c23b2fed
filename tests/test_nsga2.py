"""Property-based tests (hypothesis) for the NSGA-II core and the
ensemble-selection invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.nsga2 import (NSGAConfig, crowding_distance, dominance,
                              nondominated_rank, repair_k, run_nsga2)
from repro.core.objectives import (ensemble_accuracy, member_accuracy,
                                   population_objectives, similarity_matrix)
from repro.core.selection import select_ensemble


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 40), st.integers(2, 4), st.integers(0, 1000))
def test_front0_is_truly_nondominated(P, n_obj, seed):
    objs = jnp.asarray(np.random.default_rng(seed).normal(size=(P, n_obj)))
    ranks = np.asarray(nondominated_rank(objs))
    dom = np.asarray(dominance(objs))
    for i in np.where(ranks == 0)[0]:
        assert not dom[:, i].any(), "front-0 member is dominated"
    # every non-front-0 member is dominated by someone in a lower rank
    for i in np.where(ranks > 0)[0]:
        dominators = np.where(dom[:, i])[0]
        assert (ranks[dominators] < ranks[i]).any()


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 10), st.integers(12, 64), st.integers(0, 1000))
def test_repair_k_exact(k, M, seed):
    key = jax.random.PRNGKey(seed)
    pop = (jax.random.uniform(key, (17, M)) < 0.5).astype(jnp.float32)
    rep = repair_k(pop, key, k)
    counts = np.asarray(jnp.sum(rep, axis=1))
    assert (counts == k).all()
    # bits that were set and survive must be a subset when k >= popcount
    both = np.asarray(jnp.sum(rep * pop, axis=1))
    orig = np.asarray(jnp.sum(pop, axis=1))
    assert (both >= np.minimum(orig, k) - 1e-6).all()


def test_crowding_boundary_is_infinite():
    objs = jnp.asarray([[0.0, 1.0], [0.5, 0.5], [1.0, 0.0]])
    ranks = jnp.zeros((3,), jnp.int32)
    d = np.asarray(crowding_distance(objs, ranks))
    assert d[0] > 1e8 and d[2] > 1e8
    assert d[1] < 1e8


def test_nsga_improves_over_random():
    """Final front should (weakly) push out a random population on both
    objectives for a separable synthetic problem."""
    M = 32
    rng = np.random.default_rng(0)
    acc = jnp.asarray(rng.uniform(0.3, 0.9, M).astype(np.float32))
    S = jnp.asarray(np.eye(M, dtype=np.float32) * 0.5 + 0.5)

    def eval_fn(pop):
        s, d = population_objectives(pop, acc, S)
        return jnp.stack([s, d], axis=1)

    out = run_nsga2(eval_fn, M, NSGAConfig(pop_size=32, generations=30, k=5, seed=0))
    best_strength = float(jnp.max(out["objs"][:, 0]))
    # random k=5 baseline
    key = jax.random.PRNGKey(1)
    rnd = repair_k((jax.random.uniform(key, (256, M)) < 0.5).astype(jnp.float32), key, 5)
    rnd_best = float(jnp.max(eval_fn(rnd)[:, 0]))
    assert best_strength >= rnd_best - 1e-6
    # with S constant off-diagonal, max strength = mean of top-5 accs
    top5 = float(jnp.mean(jnp.sort(acc)[-5:]))
    assert best_strength > top5 - 0.02


def test_selection_prefers_good_local_models_negative_transfer_guard():
    """Crafted bench: client's own 3 models are good on its distribution,
    7 peer models are adversarially bad. Selection must go (mostly) local
    — the paper's negative-transfer safety valve."""
    rng = np.random.default_rng(0)
    V, C = 256, 10
    labels = rng.integers(0, C, V)
    probs = np.zeros((10, V, C), np.float32)
    for m in range(3):  # local: 85% correct
        correct = rng.random(V) < 0.85
        pred = np.where(correct, labels, (labels + 1 + m) % C)
        probs[m, np.arange(V), pred] = 1.0
    for m in range(3, 10):  # peers: 15% correct (worse than chance x1.5)
        correct = rng.random(V) < 0.15
        pred = np.where(correct, labels, (labels + m) % C)
        probs[m, np.arange(V), pred] = 1.0
    sel = select_ensemble(jnp.asarray(probs), jnp.asarray(labels),
                          NSGAConfig(pop_size=32, generations=30, k=3, seed=0))
    chrom = np.asarray(sel["chromosome"])
    assert chrom.sum() == 3
    assert chrom[:3].sum() >= 2, f"selected {chrom} — negative transfer!"
    assert float(sel["val_accuracy"]) > 0.8


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 8), st.integers(16, 64), st.integers(2, 6), st.integers(0, 99))
def test_objective_consistency_padding(M, V, C, seed):
    """Padding validation samples with label -1 must not change objectives."""
    rng = np.random.default_rng(seed)
    probs = rng.dirichlet(np.ones(C), size=(M, V)).astype(np.float32)
    labels = rng.integers(0, C, V)
    pop = (rng.random((9, M)) < 0.5).astype(np.float32)
    pop[0, :] = 1.0  # never all-zero
    a0 = member_accuracy(jnp.asarray(probs), jnp.asarray(labels))
    pp = np.pad(probs, ((0, 0), (0, 13), (0, 0)))
    ll = np.pad(labels, (0, 13), constant_values=-1)
    a1 = member_accuracy(jnp.asarray(pp), jnp.asarray(ll))
    np.testing.assert_allclose(np.asarray(a0), np.asarray(a1), atol=1e-6)
    e0 = ensemble_accuracy(jnp.asarray(pop), jnp.asarray(probs), jnp.asarray(labels))
    e1 = ensemble_accuracy(jnp.asarray(pop), jnp.asarray(pp), jnp.asarray(ll))
    np.testing.assert_allclose(np.asarray(e0), np.asarray(e1), atol=1e-6)
    s0 = similarity_matrix(jnp.asarray(probs))
    s1 = similarity_matrix(jnp.asarray(pp), jnp.asarray(ll))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-5)


def test_kernel_backed_selection_matches_jnp():
    rng = np.random.default_rng(3)
    probs = rng.dirichlet(np.ones(5), size=(12, 128)).astype(np.float32)
    labels = rng.integers(0, 5, 128)
    cfg = NSGAConfig(pop_size=32, generations=10, k=4, seed=7)
    s_jnp = select_ensemble(jnp.asarray(probs), jnp.asarray(labels), cfg,
                            use_kernel=False)
    s_ker = select_ensemble(jnp.asarray(probs), jnp.asarray(labels), cfg,
                            use_kernel=True)
    np.testing.assert_array_equal(np.asarray(s_jnp["chromosome"]),
                                  np.asarray(s_ker["chromosome"]))
