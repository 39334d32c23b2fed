"""Property-based tests (hypothesis) for the NSGA-II core and the
ensemble-selection invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.nsga2 import (NSGAConfig, crowding_distance, dominance,
                              keep_top_k, nondominated_rank, repair_k,
                              run_nsga2, run_nsga2_batched, survival_order)
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


@pytest.mark.parametrize("row,k,want", [
    ([3.0, 2.0, 2.0, 2.0, 1.0], 2, [1, 1, 0, 0, 0]),
    ([2.5, 2.5, 2.5, 0.1, 0.2], 2, [1, 1, 0, 0, 0]),
    ([0.1, 2.5, 0.3, 2.5, 2.5], 1, [0, 1, 0, 0, 0]),
    ([0.4, 0.9, 0.2, 0.7, 0.3], 3, [1, 1, 0, 1, 0]),
])
def test_keep_top_k_gives_k_ones_when_scores_tie(row, k, want):
    """A tie at the k-th largest score keeps the leftmost (the exact-k
    repair's float32 noise ties about once in a million rows)."""
    got = np.asarray(keep_top_k(jnp.asarray([row], jnp.float32), k))
    np.testing.assert_array_equal(got, [want])


def _pool(draw_seed, P, n_obj, grid):
    """(P, n_obj) float32 objectives; with `grid`, drawn from a few values
    so that duplicates and tied objectives are common."""
    rng = np.random.default_rng(draw_seed)
    if grid:
        return rng.integers(0, grid, size=(P, n_obj)).astype(np.float32) / grid
    return rng.normal(size=(P, n_obj)).astype(np.float32)


pools = st.tuples(st.integers(0, 10_000), st.sampled_from([2, 3, 7, 16, 40]),
                  st.integers(2, 3), st.sampled_from([0, 3, 6]))
rank = jax.jit(nondominated_rank, static_argnames="stop_at")


@settings(max_examples=40, deadline=None)
@given(pools, st.floats(0.0, 1.0))
def test_truncated_rank_agrees_with_full_rank(pool, frac):
    """Peeling stops once `stop_at` members have a front: every ranked
    member has its full rank, at least `stop_at` are ranked, the fronts
    ranked are whole, and the rest keep the sentinel P."""
    seed, P, n_obj, grid = pool
    objs = jnp.asarray(_pool(seed, P, n_obj, grid))
    s = 1 + int(frac * (P - 1))
    full = np.asarray(rank(objs))
    trunc = np.asarray(rank(objs, stop_at=s))
    ranked = trunc < P
    np.testing.assert_array_equal(trunc[ranked], full[ranked])
    assert (trunc[~ranked] == P).all()
    assert ranked.sum() >= s
    if (~ranked).any():
        assert full[~ranked].min() > full[ranked].max()


@jax.jit
def _survival(objs, ranks):
    return survival_order(ranks, crowding_distance(objs, ranks), objs)[0]


def _survivors(objs, ranks, n):
    return np.asarray(_survival(objs, ranks))[:n]


@settings(max_examples=40, deadline=None)
@given(pools)
def test_truncated_rank_keeps_the_same_survivors(pool):
    """Survival of P from a 2P pool picks the same members, in the same
    order, whether the pool is ranked whole or only until P have a front."""
    seed, P, n_obj, grid = pool
    objs = jnp.asarray(_pool(seed, 2 * P, n_obj, grid))
    full = _survivors(objs, rank(objs), P)
    trunc = _survivors(objs, rank(objs, stop_at=P), P)
    np.testing.assert_array_equal(trunc, full)


@settings(max_examples=40, deadline=None)
@given(pools)
def test_survivors_pool_ranks_are_their_own_ranks(pool):
    """The carry's lemma: every front before the last one admitted
    survives whole, so a survivor's rank in the pool is its rank among
    the survivors."""
    seed, P, n_obj, grid = pool
    objs = jnp.asarray(_pool(seed, 2 * P, n_obj, grid))
    ranks = rank(objs, stop_at=P)
    keep = _survivors(objs, ranks, P)
    np.testing.assert_array_equal(np.asarray(ranks)[keep],
                                  np.asarray(rank(objs[keep])))


def test_survival_admits_front0_whole_before_front1():
    """A front-1 boundary member has crowding 2·BIG; the old key
    `rank * BIG - crowd` put it ahead of front 0's interior members, so a
    dominated member survived while nondominated ones were dropped."""
    objs = jnp.asarray([[0, 4], [1, 3], [2, 2], [3, 1], [4, 0],   # front 0
                        [0, 3], [3, 0], [1, 1]], jnp.float32)     # front 1
    ranks = nondominated_rank(objs)
    np.testing.assert_array_equal(np.asarray(ranks), [0] * 5 + [1] * 3)
    crowd = crowding_distance(objs, ranks)
    old = np.argsort(np.asarray(ranks.astype(jnp.float32) * 1e9 - crowd))
    assert np.asarray(ranks)[old[:4]].max() == 1     # the fault, as it was
    order, r_sorted, c_sorted, o_sorted = survival_order(ranks, crowd, objs)
    order = np.asarray(order)
    assert (np.diff(np.asarray(ranks)[order]) >= 0).all()
    np.testing.assert_array_equal(np.sort(order[:5]), [0, 1, 2, 3, 4])
    # the state sorted along is the members' own
    np.testing.assert_array_equal(np.asarray(r_sorted), np.asarray(ranks)[order])
    np.testing.assert_array_equal(np.asarray(c_sorted), np.asarray(crowd)[order])
    np.testing.assert_array_equal(np.asarray(o_sorted), np.asarray(objs)[order])


def _crowding_ref(objs, ranks):
    """Crowding in float32 numpy, each front put in (value, index) order."""
    objs, ranks = np.asarray(objs), np.asarray(ranks)
    dist = np.zeros(len(ranks), np.float32)
    for m in range(objs.shape[1]):
        v = objs[:, m]
        span = np.maximum(v.max() - v.min(), np.float32(1e-12))
        contrib = np.zeros(len(ranks), np.float32)
        for r in np.unique(ranks):
            members = np.flatnonzero(ranks == r)
            front = members[np.lexsort((members, v[members]))]
            contrib[front[0]] = contrib[front[-1]] = np.float32(1e9)
            for a, i, b in zip(front, front[1:], front[2:]):
                contrib[i] = (v[b] - v[a]) / span
        dist = dist + contrib
    return dist


@settings(max_examples=40, deadline=None)
@given(pools, st.integers(1, 6))
def test_crowding_orders_every_front_by_value(pool, n_fronts):
    """Inside fronts of rank >= 1 too, neighbours are neighbours in value:
    the old key `rank * BIG + value` lost the value at every rank >= 1
    (float32's spacing near 1e9 is 64), leaving index order."""
    seed, P, n_obj, grid = pool
    objs = _pool(seed, P, n_obj, grid)
    ranks = np.random.default_rng(seed + 1).integers(0, n_fronts, P)
    np.testing.assert_array_equal(
        np.asarray(crowding_distance(jnp.asarray(objs),
                                     jnp.asarray(ranks, jnp.int32))),
        _crowding_ref(objs, ranks))


def test_crowding_in_a_higher_front_follows_value_not_index():
    objs = jnp.asarray([[0.9, 0.1], [0.1, 0.9], [0.5, 0.5], [0.3, 0.7]])
    for r in (0, 1, 5):
        d = np.asarray(crowding_distance(objs, jnp.full((4,), r, jnp.int32)))
        assert d[0] > 1e8 and d[1] > 1e8
        np.testing.assert_allclose(d[2:], [2 * 0.6 / 0.8, 2 * 0.4 / 0.8],
                                   rtol=1e-6)


@pytest.mark.parametrize("tied", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_ga_returns_objectives_and_ranks_of_its_population(tied, masked):
    """The carried objectives and pool ranks of the final population are
    what a fresh evaluation and a full rank of it give, bit for bit
    (`tied`: accuracies on a coarse grid, so many ensembles tie)."""
    N, M, cfg = 3, 16, NSGAConfig(pop_size=24, generations=12, k=4)
    rng = np.random.default_rng(5)
    acc = rng.uniform(0.3, 0.9, (N, M)).astype(np.float32)
    S = rng.uniform(0.0, 1.0, (N, M, M)).astype(np.float32)
    if tied:
        acc = np.round(acc * 4) / 4
    S = jnp.asarray((S + np.swapaxes(S, 1, 2)) / 2)
    acc = jnp.asarray(acc)
    mask = (jnp.asarray(rng.random((N, M)) < 0.7, jnp.float32)
            .at[:, :cfg.k].set(1.0) if masked else None)

    def eval_fn(pop):
        st_, dv = jax.vmap(population_objectives)(pop, acc, S)
        return jnp.stack([st_, dv], axis=2)

    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(N))
    out = jax.jit(lambda k, vm: run_nsga2_batched(
        eval_fn, M, cfg, k, valid_mask=vm))(keys, mask)
    np.testing.assert_array_equal(np.asarray(out["objs"]),
                                  np.asarray(jax.jit(eval_fn)(out["pop"])))
    np.testing.assert_array_equal(
        np.asarray(out["ranks"]),
        np.asarray(jax.vmap(nondominated_rank)(out["objs"])))
    sweeps = np.asarray(out["rank_sweeps"])
    assert sweeps.shape == (N,)
    assert (sweeps >= cfg.generations + 1).all()


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
