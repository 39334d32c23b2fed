"""The batched selection engine: vmapped-vs-serial equivalence, batched
Pallas parity, store semantics (masked lazy fetch, empty-selection
fallback), scheduler batching, and async-driver determinism — all on
synthetic prediction matrices (no CNN training)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bench import BenchEntry, PredictionStore, stack_stores
from repro.core.engine import SelectionEngine
from repro.core.nsga2 import NSGAConfig, client_keys
from repro.core.selection import select_ensemble, select_ensembles
from repro.fl.scheduler import AsyncConfig, simulate_async
from repro.fl.topology import make_topology

N_CLIENTS, M_PER, V, C = 4, 3, 96, 5
CFG = NSGAConfig(pop_size=32, generations=10, k=3, seed=7)


def _pred_matrix(rng, quality, labels):
    """(V, C) probabilities that agree with `labels` w.p. `quality`."""
    correct = rng.random(len(labels)) < quality
    pred = np.where(correct, labels, (labels + 1 + rng.integers(0, C - 1,
                                                                len(labels))) % C)
    out = np.full((len(labels), C), 0.05, np.float32)
    out[np.arange(len(labels)), pred] = 0.8
    return out / out.sum(1, keepdims=True)


def _make_world(seed=0, n_clients=N_CLIENTS):
    """Synthetic network: per-client labels + per-(client, model) pred
    matrices; local models are better than remote ones on average."""
    rng = np.random.default_rng(seed)
    capacity = n_clients * M_PER
    labels = {c: rng.integers(0, C, V) for c in range(n_clients)}
    quality = {}
    mats = {}
    for c in range(n_clients):
        for owner in range(n_clients):
            for m in range(M_PER):
                slot = owner * M_PER + m
                q = rng.uniform(0.6, 0.9) if owner == c else rng.uniform(0.2, 0.8)
                quality[(c, slot)] = q
                mats[(c, slot)] = _pred_matrix(rng, q, labels[c])
    return capacity, labels, mats


def _entry(owner, m, predict=None, calls=None):
    slot = owner * M_PER + m

    def _predict(x, slot=slot):
        if calls is not None:
            calls.append(slot)
        return np.full((len(x), C), 1.0 / C, np.float32)

    return BenchEntry(model_id=slot, owner=owner, family=f"f{m}",
                      predict=predict or _predict)


def _full_stores(capacity, labels, mats, n_clients=N_CLIENTS, calls=None):
    stores = []
    for c in range(n_clients):
        s = PredictionStore(c, capacity, np.zeros((V, 2), np.float32),
                            labels[c], C)
        for owner in range(n_clients):
            for m in range(M_PER):
                slot = owner * M_PER + m
                s.add(_entry(owner, m, calls=calls), preds=mats[(c, slot)])
        stores.append(s)
    return stores


# ---------------------------------------------------------------- selection

def test_vmapped_matches_serial_per_client():
    """One vmapped NSGA-II run == N serial runs with the same per-client
    PRNG streams, chromosome for chromosome."""
    capacity, labels, mats = _make_world()
    stores = _full_stores(capacity, labels, mats)
    preds, labs, masks = stack_stores(stores)
    keys = client_keys(CFG.seed, np.arange(N_CLIENTS))
    batched = select_ensembles(jnp.asarray(preds), jnp.asarray(labs), CFG,
                               keys=keys, model_mask=jnp.asarray(masks))
    for c in range(N_CLIENTS):
        serial = select_ensemble(jnp.asarray(preds[c]), jnp.asarray(labs[c]),
                                 CFG, key=keys[c],
                                 model_mask=jnp.asarray(masks[c]))
        np.testing.assert_array_equal(np.asarray(serial["chromosome"]),
                                      np.asarray(batched["chromosome"][c]))
        np.testing.assert_allclose(float(serial["val_accuracy"]),
                                   float(batched["val_accuracy"][c]),
                                   atol=1e-6)


def test_vmapped_kernel_path_matches_jnp_path():
    """use_kernel=True routes every objective evaluation through ONE
    batched Pallas launch. Exact objective parity is asserted in
    test_kernels; here we check the full GA outcome is equivalent —
    1-ulp eval ties may flip individual sort orders, but every client
    must land on an equally good exact-k ensemble."""
    capacity, labels, mats = _make_world(seed=3)
    stores = _full_stores(capacity, labels, mats)
    preds, labs, masks = stack_stores(stores)
    a = select_ensembles(jnp.asarray(preds), jnp.asarray(labs), CFG,
                         use_kernel=False, model_mask=jnp.asarray(masks))
    b = select_ensembles(jnp.asarray(preds), jnp.asarray(labs), CFG,
                         use_kernel=True, model_mask=jnp.asarray(masks))
    chrom_b = np.asarray(b["chromosome"])
    assert (chrom_b.sum(1) == CFG.k).all()
    np.testing.assert_allclose(np.asarray(a["val_accuracy"]),
                               np.asarray(b["val_accuracy"]), atol=0.02)
    np.testing.assert_allclose(np.asarray(a["member_acc"]),
                               np.asarray(b["member_acc"]), atol=1e-6)


def test_per_client_prng_streams_differ():
    keys = np.asarray(client_keys(0, np.arange(8)))
    assert len({tuple(k) for k in keys}) == 8


def test_masked_slots_never_selected():
    """Slots whose predictions have not arrived must stay out of every
    chromosome (the async engine's partial-bench case)."""
    capacity, labels, mats = _make_world(seed=1)
    stores = _full_stores(capacity, labels, mats)
    # client 0 only ever received the first half of the network's models
    half = capacity // 2
    stores[0].mask[half:] = False
    preds, labs, masks = stack_stores(stores)
    out = select_ensembles(jnp.asarray(preds), jnp.asarray(labs), CFG,
                           model_mask=jnp.asarray(masks))
    chrom0 = np.asarray(out["chromosome"][0])
    assert chrom0[half:].sum() == 0
    assert chrom0.sum() == CFG.k


# ---------------------------------------------------------------- the store

def test_store_masked_lazy_fetch_only_evaluates_selected():
    capacity, labels, mats = _make_world()
    calls = []
    stores = _full_stores(capacity, labels, mats, calls=calls)
    calls.clear()  # adds used preds=..., so no predict calls yet
    mask = np.zeros(capacity, bool)
    mask[[1, 4]] = True
    out = stores[0].predictions(np.zeros((7, 2), np.float32), mask=mask)
    assert out.shape == (capacity, 7, C)
    assert sorted(calls) == [1, 4]
    assert (out[[0, 2, 3]] == 0).all()


def test_store_empty_mask_returns_zeros_not_none():
    """Regression: the old ModelBench returned None for an all-False mask
    and the driver crashed multiplying it."""
    capacity, labels, mats = _make_world()
    stores = _full_stores(capacity, labels, mats)
    out = stores[0].predictions(np.zeros((5, 2), np.float32),
                                mask=np.zeros(capacity, bool))
    assert out is not None and out.shape == (capacity, 5, C)
    assert (out == 0).all()


def test_empty_selection_falls_back_to_local_only():
    """An all-zero chromosome (e.g. free-size GA collapse) must serve the
    local-only fallback ensemble, not crash or return a zero vote."""
    capacity, labels, mats = _make_world()
    stores = _full_stores(capacity, labels, mats)
    engine = SelectionEngine(stores, CFG, ensemble_k=CFG.k)
    engine.results[2] = {"chromosome": np.zeros(capacity, np.float32)}
    x = np.zeros((6, 2), np.float32)
    vote, chrom = engine.serve(2, x)
    assert chrom.sum() == CFG.k
    assert (np.where(chrom > 0.5)[0] // M_PER == 2).all()  # all local slots
    assert np.isfinite(vote).all() and (vote.sum(1) > 0).all()


def test_local_fallback_never_pads_with_remote_models():
    """A client with fewer than ensemble_k local models must get a
    SMALLER local-only fallback, not one padded with arbitrary remote
    slots (the negative-transfer valve's whole point)."""
    capacity, labels, mats = _make_world()
    stores = _full_stores(capacity, labels, mats)
    stores[2].mask[2 * M_PER + 2] = False  # client 2: only 2 locals left
    engine = SelectionEngine(stores, CFG, ensemble_k=CFG.k)
    chrom = engine.chromosome(2)
    sel = np.flatnonzero(chrom > 0.5)
    assert len(sel) == 2  # not padded up to k=3
    assert all(s // M_PER == 2 for s in sel)
    vote, _ = engine.serve(2, np.zeros((4, 2), np.float32))
    assert np.isfinite(vote).all()


def test_stack_stores_alignment():
    capacity, labels, mats = _make_world()
    stores = _full_stores(capacity, labels, mats)
    preds, labs, masks = stack_stores(stores, clients=[2, 0])
    assert preds.shape[0] == 2 and preds.shape[1] == capacity
    np.testing.assert_array_equal(labs[0][:V], labels[2])
    np.testing.assert_array_equal(preds[1, 5, :V], mats[(0, 5)])
    assert masks.all()


# ------------------------------------------------------------ async engine

def _drive_async(seed=0):
    capacity, labels, mats = _make_world(seed=5)
    stores = [PredictionStore(c, capacity, np.zeros((V, 2), np.float32),
                              labels[c], C) for c in range(N_CLIENTS)]
    engine = SelectionEngine(stores, CFG, ensemble_k=CFG.k)
    batch_sizes = []

    def on_add(c, key, t):
        owner, m = key
        slot = owner * M_PER + m
        stores[c].add(_entry(owner, m), preds=mats[(c, slot)])

    def on_select_batch(clients, bench_ids, t):
        batch_sizes.append(len(clients))
        return {c: float(r["val_accuracy"])
                for c, r in engine.select(clients).items()}

    acfg = AsyncConfig(n_clients=N_CLIENTS, models_per_client=M_PER,
                       select_debounce=0.25, seed=seed)
    nb = make_topology("full", N_CLIENTS)
    trace = simulate_async(acfg, nb, train_cost=lambda c, m: 1.0 + 0.2 * m,
                           on_add=on_add, on_select_batch=on_select_batch)
    return trace, engine, batch_sizes


def test_async_selection_is_batched():
    """Quantized debounce must coalesce same-window arrivals: at least one
    select call covers several clients at once."""
    _, _, batch_sizes = _drive_async()
    assert max(batch_sizes) >= 2


def test_async_driver_deterministic():
    t1, e1, _ = _drive_async(seed=0)
    t2, e2, _ = _drive_async(seed=0)
    assert t1.selections == t2.selections
    assert t1.events == t2.events
    for c in range(N_CLIENTS):
        np.testing.assert_array_equal(e1.chromosome(c), e2.chromosome(c))


def test_async_quality_curves_recorded():
    """The unified engine produces real val-accuracy-over-virtual-time
    curves for every client (the trace-only days are over)."""
    trace, engine, _ = _drive_async()
    for c in range(N_CLIENTS):
        assert len(trace.selections[c]) >= 1
        ts = [t for t, _ in trace.selections[c]]
        assert ts == sorted(ts)
        accs = [a for _, a in trace.selections[c]]
        assert all(0.0 <= a <= 1.0 for a in accs)
        # final chromosome selects exactly k arrived models
        assert engine.chromosome(c).sum() == CFG.k


def test_async_final_state_matches_sync_selection():
    """Once every model has arrived, the async engine's answer equals the
    one-shot sync selection (same stores, same per-client streams)."""
    _, engine_async, _ = _drive_async()
    capacity, labels, mats = _make_world(seed=5)
    stores = _full_stores(capacity, labels, mats)
    engine_sync = SelectionEngine(stores, CFG, ensemble_k=CFG.k)
    engine_sync.select()
    for c in range(N_CLIENTS):
        np.testing.assert_array_equal(engine_async.chromosome(c),
                                      engine_sync.chromosome(c))


def test_select_counts_rank_sweeps_and_keeps_them_out_of_results():
    """Each select adds its NSGA-II's peeling sweeps (the most over its
    real lanes) to `n_rank_sweeps` and `engine.select.rank_sweeps`; with
    the pool peeled only until P members have a front, a select at
    P = G = 100 needs about one sweep a generation, under 3·G. No
    per-client result carries the count."""
    from repro.obs.metrics import Metrics
    nsga = NSGAConfig(pop_size=100, generations=100, k=5, seed=7)
    capacity, labels, mats = _make_world(seed=2, n_clients=8)
    engine = SelectionEngine(_full_stores(capacity, labels, mats,
                                          n_clients=8), nsga,
                             metrics=Metrics())
    seen = []
    for clients in ([0, 1, 2], [5]):
        fresh = engine.select(clients)
        counted = engine.metrics.frame().scalars["engine.select.rank_sweeps"]
        seen.append((engine.n_rank_sweeps, counted))
        assert all("rank_sweeps" not in r for r in fresh.values())
        assert all("rank_sweeps" not in r for r in engine.results.values())
    (first, c1), (both, c2) = seen
    assert c1 == first and c2 == both
    assert nsga.generations < first < 3 * nsga.generations
    assert first < both
