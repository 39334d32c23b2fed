"""Device-resident incremental selection state (DESIGN.md §7):
incremental-vs-recompute parity after randomized add/evict/churn
sequences, identical selections through cached stats, donation safety,
eviction invalidation, the engine-wide v_max guard, and the batched
same-family serving path."""
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bench import (BenchEntry, PredictionStore,
                              StreamingPredictionStore, stack_stores)
from repro.core.device_store import DeviceStoreBatch, _expand, _flush, _pow2
from repro.core.engine import SelectionEngine
from repro.core.nsga2 import NSGAConfig, client_keys
from repro.core.selection import (select_ensembles,
                                  select_ensembles_from_stats,
                                  selection_stats)
from repro.obs.metrics import NULL_METRICS, Metrics

N, CAP, V, C = 4, 8, 96, 5
CFG = NSGAConfig(pop_size=16, generations=6, k=3, seed=3)


def _entry(mid, owner=None, predict=None):
    return BenchEntry(model_id=mid, owner=mid if owner is None else owner,
                      family="f",
                      predict=predict or (lambda x: np.full(
                          (len(x), C), 1.0 / C, np.float32)))


def _rand_preds(rng):
    p = rng.random((V, C)).astype(np.float32)
    return p / p.sum(1, keepdims=True)


def _fresh_stores(seed=0, streaming=True, n=N):
    rng = np.random.default_rng(seed)
    cls = StreamingPredictionStore if streaming else PredictionStore
    return [cls(c, CAP, np.zeros((V, 2), np.float32),
                rng.integers(0, C, V), C) for c in range(n)], rng


def _full_rebuild_stats(stores, v_max):
    preds, labels, masks = stack_stores(stores, v_to=v_max)
    acc, S = selection_stats(jnp.asarray(preds), jnp.asarray(labels))
    return preds, labels, masks, np.asarray(acc), np.asarray(S)


def _churn(stores, rng, dev=None, n_ops=60, flush_every=7):
    """Randomized adds (with eviction pressure: 3x more global ids than
    physical slots), interleaved with device flushes."""
    for op in range(n_ops):
        c = int(rng.integers(0, len(stores)))
        gid = int(rng.integers(0, 3 * CAP))
        stores[c].add(_entry(gid, owner=gid % len(stores)),
                      preds=_rand_preds(rng), t=float(op))
        if dev is not None and op % flush_every == 0:
            dev.flush()


# ------------------------------------------------- incremental parity

def test_incremental_stats_match_full_rebuild():
    """After a randomized add/evict/churn sequence with interleaved
    flushes, the cached device acc/S equal a from-scratch stack_stores +
    full-stats rebuild to fp32 tolerance."""
    stores, rng = _fresh_stores(seed=1)
    dev = DeviceStoreBatch(stores)
    _churn(stores, rng, dev=dev)
    dev.flush()
    assert sum(s.evictions for s in stores) > 0  # churn actually evicted
    preds, labels, masks, acc_full, S_full = _full_rebuild_stats(
        stores, dev.v_max)
    np.testing.assert_array_equal(np.asarray(dev.preds), preds)
    np.testing.assert_array_equal(np.asarray(dev.masks), masks)
    np.testing.assert_allclose(np.asarray(dev.acc), acc_full, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dev.S), S_full, atol=1e-5)


def test_incremental_selection_identical_to_recompute():
    """Selections through the cached stats equal (i) a fresh
    DeviceStoreBatch flushed once from the final store state and (ii) the
    full select_ensembles recompute — chromosome for chromosome."""
    stores, rng = _fresh_stores(seed=2)
    dev = DeviceStoreBatch(stores)
    _churn(stores, rng, dev=dev)
    dev.flush()
    keys = client_keys(CFG.seed, np.arange(N))
    preds_i, labels_i, masks_i, acc_i, S_i = dev.gather(np.arange(N))
    inc = select_ensembles_from_stats(acc_i, S_i, preds_i, labels_i, CFG,
                                      keys=keys, model_mask=masks_i)

    fresh = DeviceStoreBatch(stores)  # from-scratch: every slot re-flushed
    fresh.flush()
    np.testing.assert_array_equal(np.asarray(dev.acc), np.asarray(fresh.acc))
    np.testing.assert_array_equal(np.asarray(dev.S), np.asarray(fresh.S))

    preds, labels, masks = stack_stores(stores, v_to=dev.v_max)
    full = select_ensembles(jnp.asarray(preds), jnp.asarray(labels), CFG,
                            keys=keys, model_mask=jnp.asarray(masks))
    np.testing.assert_array_equal(np.asarray(inc["chromosome"]),
                                  np.asarray(full["chromosome"]))
    np.testing.assert_allclose(np.asarray(inc["val_accuracy"]),
                               np.asarray(full["val_accuracy"]), atol=1e-6)


def test_engine_incremental_matches_restack_engine():
    """The engine's device-resident path and the legacy restack path pick
    identical ensembles for the same store state and seeds."""
    stores_a, rng_a = _fresh_stores(seed=4)
    stores_b, rng_b = _fresh_stores(seed=4)
    eng_inc = SelectionEngine(stores_a, CFG, ensemble_k=CFG.k)
    eng_re = SelectionEngine(stores_b, CFG, ensemble_k=CFG.k,
                             device_resident=False)
    assert eng_inc.device is not None and eng_re.device is None
    _churn(stores_a, rng_a)
    _churn(stores_b, rng_b)
    # selects along the way stamp contribution stats (eviction input), so
    # they must run on BOTH engines to keep the fleets comparable — and
    # the intermediate answers must already agree
    for _ in range(2):
        ra = eng_inc.select(t=1.0)
        rb = eng_re.select(t=1.0)
        for c in ra:
            np.testing.assert_array_equal(ra[c]["chromosome"],
                                          rb[c]["chromosome"])
        _churn(stores_a, rng_a, n_ops=10)
        _churn(stores_b, rng_b, n_ops=10)
    eng_inc.select(t=2.0)
    eng_re.select(t=2.0)
    for c in range(N):
        np.testing.assert_array_equal(eng_inc.results[c]["chromosome"],
                                      eng_re.results[c]["chromosome"])
        np.testing.assert_allclose(eng_inc.results[c]["member_acc"],
                                   eng_re.results[c]["member_acc"],
                                   atol=1e-5)


# ------------------------------------------------- eviction coherence

def test_eviction_zeroes_device_row_and_stats():
    """slot_gen bumps (evictions) must zero the device row and drop the
    cached similarity row/column on the next flush."""
    stores, rng = _fresh_stores(seed=5, n=1)
    s = stores[0]
    for gid in range(CAP):
        s.add(_entry(gid, owner=1), preds=_rand_preds(rng), t=float(gid))
    dev = DeviceStoreBatch(stores)
    dev.flush()
    assert float(jnp.abs(dev.S).sum()) > 0
    gen_before = s.slot_gen.copy()
    s.add(_entry(CAP + 1, owner=1), preds=_rand_preds(rng), t=99.0)  # evicts
    evicted = int(np.flatnonzero(s.slot_gen != gen_before)[0])
    victim_gid = [g for g, sl in s.slot_of.items() if sl == evicted]
    assert victim_gid == [CAP + 1]  # slot now remapped to the newcomer
    # evict WITHOUT refilling: drop the newcomer again via direct evict
    slot2 = s._evict_one()
    dev.flush()
    np.testing.assert_array_equal(np.asarray(dev.preds[0, slot2]), 0.0)
    assert float(dev.masks[0, slot2]) == 0.0
    np.testing.assert_array_equal(np.asarray(dev.S[0, slot2, :]), 0.0)
    np.testing.assert_array_equal(np.asarray(dev.S[0, :, slot2]), 0.0)


# ------------------------------------------------- flush mechanics

def test_flush_noop_and_dirty_counting():
    stores, rng = _fresh_stores(seed=6)
    dev = DeviceStoreBatch(stores)
    stores[0].add(_entry(0), preds=_rand_preds(rng))
    stores[2].add(_entry(5), preds=_rand_preds(rng))
    assert dev.flush() == 2          # exactly the two dirty slots
    assert dev.flush() == 0          # clean: no-op, no jit launch
    n = dev.n_flushes
    dev.flush()
    assert dev.n_flushes == n        # no-op did not count as a flush
    stores[1].add(_entry(3), preds=_rand_preds(rng))
    assert dev.flush() == 1          # only the changed row is scattered


def test_skewed_dirty_widths_bucket_into_separate_flushes():
    """One bursty client (churn join: every slot dirty) must not inflate
    the padded width of every other client's group — groups bucket by
    their own pow2 width, and parity still holds."""
    stores, rng = _fresh_stores(seed=11)
    dev = DeviceStoreBatch(stores)
    for c in range(N):                       # light dirt everywhere
        stores[c].add(_entry(0), preds=_rand_preds(rng))
    for gid in range(CAP):                   # burst on client 2
        stores[2].add(_entry(gid), preds=_rand_preds(rng))
    n0 = dev.n_flushes
    assert dev.flush() == (N - 1) + CAP
    assert dev.n_flushes - n0 == 2           # width-1 and width-CAP buckets
    _, _, masks_f, acc_f, S_f = _full_rebuild_stats(stores, dev.v_max)
    np.testing.assert_array_equal(np.asarray(dev.masks), masks_f)
    np.testing.assert_allclose(np.asarray(dev.acc), acc_f, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dev.S), S_f, atol=1e-5)


def test_two_device_batches_track_one_fleet_independently():
    """The dirty log is multi-consumer: a second DeviceStoreBatch over
    the same stores must see every event the first one drained."""
    stores, rng = _fresh_stores(seed=12)
    a = DeviceStoreBatch(stores)
    b = DeviceStoreBatch(stores)
    stores[1].add(_entry(3), preds=_rand_preds(rng))
    assert a.flush() == 1                    # A drains first...
    assert b.flush() == 1                    # ...B still sees the event
    np.testing.assert_array_equal(np.asarray(a.masks), np.asarray(b.masks))
    np.testing.assert_array_equal(np.asarray(a.acc), np.asarray(b.acc))
    np.testing.assert_array_equal(np.asarray(a.S), np.asarray(b.S))


def test_donation_safety_no_use_after_donate():
    """The flush donates its buffers: the batch must adopt the returned
    arrays (never touch the donated handles again) and keep answering
    correctly across repeated flush/gather cycles."""
    stores, rng = _fresh_stores(seed=7)
    _churn(stores, rng, n_ops=20)
    dev = DeviceStoreBatch(stores)
    for round_ in range(3):
        old = (dev.preds, dev.masks, dev.acc, dev.S)
        _churn(stores, rng, n_ops=5)
        dev.flush()
        assert all(new is not o for new, o in
                   zip((dev.preds, dev.masks, dev.acc, dev.S), old))
        # reads go through the fresh handles only — and stay correct
        _, _, masks_g, acc_g, _ = dev.gather(np.arange(N))
        _, _, masks_f, acc_f, _ = _full_rebuild_stats(stores, dev.v_max)
        np.testing.assert_array_equal(np.asarray(masks_g), masks_f)
        np.testing.assert_allclose(np.asarray(acc_g), acc_f, atol=1e-5)


def test_flush_is_donated():
    """The jitted flush really marks its five mutable buffers as donated
    (input-output aliased — the in-place device update the tentpole is
    named for); labels, nv, and the dirty rows are not, and the
    expansion of the compact upload that feeds it donates nothing."""
    n, m, v, c, k, r, u = 2, 4, 8, 3, 1, 2, 1
    args = (jnp.zeros((n, m, v, c)), jnp.zeros((n, m, v, c)),
            jnp.zeros((n, m)), jnp.zeros((n, m)), jnp.zeros((n, m, m)),
            jnp.zeros((n, v), jnp.int32), jnp.ones((n,)),
            jnp.zeros((k * r, v, c)), jnp.zeros((k * r,)),
            jnp.zeros((k,), jnp.int32), jnp.zeros((k, r), jnp.int32))
    main = [l for l in _flush.lower(*args).as_text().splitlines()
            if "@main" in l][0]
    for i in range(5):
        assert f"%arg{i}: " in main and "aliasing_output" in \
            main.split(f"%arg{i}: ")[1].split("%arg")[0]
    assert "aliasing_output" not in main.split("%arg5: ")[1]
    # the expansion of the compact upload donates nothing
    expand = _expand.lower(jnp.zeros((u, v, c)), jnp.zeros((u,)),
                           jnp.zeros((k * r,), jnp.int32)).as_text()
    assert "aliasing_output" not in expand


# ------------------------------------------------- v_max guard (churn join)

def test_late_wider_client_is_rejected_not_truncated():
    stores, rng = _fresh_stores(seed=8)
    engine = SelectionEngine(stores, CFG, ensemble_k=CFG.k)
    wide = PredictionStore(N, CAP, np.zeros((V, 2), np.float32),
                           rng.integers(0, C, 4 * V), C)
    assert wide.v_pad > engine._v_max
    with pytest.raises(ValueError, match="v_pad"):
        engine.add_store(wide)
    # the restack path refuses too (no silent truncation)
    eng_re = SelectionEngine(stores, CFG, ensemble_k=CFG.k,
                             device_resident=False)
    eng_re.stores.append(wide)
    for gid in range(CFG.k):
        wide.add(_entry(gid, owner=N), preds=np.full(
            (4 * V, C), 1.0 / C, np.float32))
    with pytest.raises(ValueError, match="v_pad"):
        eng_re.select()


def test_provisioned_v_max_admits_wider_late_joiner():
    stores, rng = _fresh_stores(seed=9)
    with pytest.raises(ValueError, match="narrower"):
        SelectionEngine(stores, CFG, v_max=32)   # below the widest store
    engine = SelectionEngine(stores, CFG, ensemble_k=CFG.k,
                             v_max=4 * V + ((-4 * V) % 128))
    _churn(stores, rng, n_ops=30)
    wide = PredictionStore(N, CAP, np.zeros((4 * V, 2), np.float32),
                           rng.integers(0, C, 4 * V), C)
    idx = engine.add_store(wide)
    assert idx == N and engine.device.preds.shape[0] == N + 1
    for gid in range(CAP):
        wide.add(_entry(gid, owner=N), preds=np.asarray(
            np.random.default_rng(0).random((4 * V, C)), np.float32))
    res = engine.select()
    assert idx in res                            # the late joiner selects
    assert res[idx]["chromosome"].sum() == CFG.k
    # its stats match a from-scratch rebuild over the grown fleet
    _, _, _, acc_f, S_f = _full_rebuild_stats(engine.stores, engine._v_max)
    np.testing.assert_allclose(np.asarray(engine.device.acc), acc_f,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(engine.device.S), S_f, atol=1e-5)


# ------------------------------------------------- batched serving path

def test_predictions_batched_same_family():
    """Same-family members carrying raw params are served through ONE
    vmapped multi-model forward; per-entry closures are never called."""
    import jax

    from repro.fl.client import predict_probs
    from repro.models.cnn import CNNConfig, init_model

    ccfg = CNNConfig(n_classes=C, width=4, in_channels=2)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    params = [init_model("cnn4", k, ccfg) for k in keys]
    x_val = np.zeros((V, 8, 8, 2), np.float32)
    store = PredictionStore(0, 4, x_val,
                            np.zeros(V, np.int64), C)
    calls = []
    for i, p in enumerate(params):
        e = BenchEntry(model_id=i, owner=0, family="cnn4",
                       predict=lambda x, p=p: calls.append(1) or
                       predict_probs("cnn4", ccfg, p, x),
                       params=p, ccfg=ccfg)
        store.add(e, preds=np.full((V, C), 1.0 / C, np.float32))
    x = np.random.default_rng(1).random((7, 8, 8, 2)).astype(np.float32)
    mask = np.array([True, True, True, False])
    out = store.predictions(x, mask=mask)
    assert calls == []               # batched path: no per-entry dispatch
    for i, p in enumerate(params):
        np.testing.assert_allclose(out[i], predict_probs("cnn4", ccfg, p, x),
                                    atol=1e-5)
    assert (out[3] == 0).all()


def test_predictions_falls_back_for_paramless_entries():
    x_val = np.zeros((V, 2), np.float32)
    store = PredictionStore(0, 3, x_val, np.zeros(V, np.int64), C)
    calls = []
    for i in range(2):
        store.add(_entry(i, owner=0,
                         predict=lambda x, i=i: calls.append(i) or np.full(
                             (len(x), C), 1.0 / C, np.float32)),
                  preds=np.full((V, C), 1.0 / C, np.float32))
    out = store.predictions(np.zeros((5, 2), np.float32))
    assert sorted(calls) == [0, 1]   # shipped closures: loop path
    assert out.shape == (3, 5, C)


# ------------------------------------------------- observability (§11)

def test_flush_counts_uploaded_and_useful_bytes():
    """Uploaded bytes are the compact row block (each distinct dirty row
    once, padded to a power of two of rows) plus its index arrays;
    useful bytes are the distinct dirty slots' real validation samples
    (sum of n_val·C·4), whatever the R/K repeats and the V pad."""
    rng = np.random.default_rng(5)
    n_val = [60, 96, 80, 40]
    stores = [PredictionStore(c, CAP, np.zeros((n, 2), np.float32),
                              rng.integers(0, C, n), C, v_pad=V)
              for c, n in enumerate(n_val)]
    dev = DeviceStoreBatch(stores)
    assert dev.flush() == 0
    assert dev.n_bytes_uploaded == dev.n_bytes_useful == 0
    dirty = {0: [0, 1, 2], 1: [0, 1, 2, 3], 2: [5, 6, 7], 3: [4]}
    for c, slots in dirty.items():
        for s in slots:
            stores[c].add(_entry(s), preds=_rand_preds(rng))
    stores[0].add(_entry(1), preds=_rand_preds(rng))   # same slot again
    mx = Metrics()
    dev.metrics = mx
    assert dev.flush() == 11
    # R=4 holds clients 0-2 (10 rows -> U=16; K=3 padded to 4), R=2
    # holds client 3 (1 row -> U=1; K=1)
    n_rows, n_padded = 16 + 1, 4 * 4 + 1 * 2
    rows = n_rows * dev.v_max * C * 4
    index = n_rows * 4 + (4 + 1) * 4 + n_padded * 4 * 2  # rmask; cu; slots, src
    useful = (3 * 60 + 4 * 96 + 3 * 80 + 1 * 40) * C * 4
    assert dev.n_bytes_uploaded == rows + index
    assert dev.n_bytes_useful == useful
    scalars = mx.frame().scalars
    assert scalars["store.upload_bytes"] == rows + index
    assert scalars["store.useful_bytes"] == useful


@pytest.mark.parametrize("dirty,uploaded,expanded", [
    # 4 clients x 5 rows (the async cell's shape): K=4, R=8, U=32
    ({0: range(5), 1: range(5), 2: range(5), 3: range(5)}, 32, 32),
    # 3 full clients: K padded 3 -> 4, R=8; 24 rows -> U=32
    ({0: range(CAP), 1: range(CAP), 3: range(CAP)}, 32, 32),
    # 3 clients x 1 row: R floored at 2, K=4; 3 rows -> U=4
    ({0: [2], 1: [0], 2: [7]}, 4, 8),
    # a 3-row client pads R to 4 and a 1-row one sits in the R=2 bucket
    ({1: [0, 3, 5], 2: [6]}, 4 + 1, 4 + 2),
])
def test_flush_counts_uploaded_and_expanded_rows(dirty, uploaded, expanded):
    """`n_rows_uploaded` sums U, the distinct dirty rows rounded up to a
    power of two, and `n_rows_expanded` the K·R padded rows the device
    expands them to; the registry mirrors both."""
    stores, rng = _fresh_stores(seed=13)
    dev = DeviceStoreBatch(stores)
    mx = Metrics()
    dev.metrics = mx
    for c, slots in dirty.items():
        for s in slots:
            stores[c].add(_entry(s), preds=_rand_preds(rng))
    assert dev.flush() == sum(len(list(v)) for v in dirty.values())
    assert (dev.n_rows_uploaded, dev.n_rows_expanded) == (uploaded, expanded)
    scalars = mx.frame().scalars
    assert scalars["store.upload_rows"] == uploaded
    assert scalars["store.expanded_rows"] == expanded


# ------------------------------------------------- compact upload

class _PaddedBlockBatch(DeviceStoreBatch):
    """The flush with the K/R padding assembled on the host: a fresh
    zero-filled (K·R, v_max, C) block, each group's rows copied in by
    fancy index, uploaded whole and scattered without an index. The
    reference the compact upload must match bit for bit."""

    def _flush_bucket(self, groups, R):
        K = _pow2(len(groups))
        groups = groups + [groups[0]] * (K - len(groups))
        all_clients = (K == len(self.stores)
                       and all(g[0] == i for i, g in enumerate(groups)))
        rows = np.zeros((K * R, self.v_max, self.n_classes), np.float32)
        rmask = np.zeros((K * R,), np.float32)
        cu = np.zeros((K,), np.int32)
        slots = np.zeros((K, R), np.int32)
        for k, (c, blk) in enumerate(groups):
            s = self.stores[c]
            cu[k] = c
            slots[k] = blk + [blk[-1]] * (R - len(blk))
            rows[k * R:(k + 1) * R, :s.v_pad] = s.preds[slots[k]]
            rmask[k * R:(k + 1) * R] = s.mask[slots[k]]
        self.preds, self.pnorm, self.masks, self.acc, self.S = _flush(
            self.preds, self.pnorm, self.masks, self.acc, self.S,
            self.labels, self.nv, *map(jnp.asarray, (rows, rmask, cu, slots)),
            all_clients=all_clients)
        self.n_flushes += 1


def _mixed_stores(seed):
    """Four streaming stores, two of them twice as wide on the validation
    axis (v_pad 256) as the others (128), so the batch pads V to 256."""
    rng = np.random.default_rng(seed)
    n_val = [96, 200, 96, 150]
    return [StreamingPredictionStore(c, CAP, np.zeros((n, 2), np.float32),
                                     rng.integers(0, C, n), C)
            for c, n in enumerate(n_val)], rng


def _add(stores, rng, c, gid, t=0.0):
    s = stores[c]
    p = rng.random((s.n_val, C)).astype(np.float32)
    s.add(_entry(gid, owner=gid % len(stores)),
          preds=p / p.sum(1, keepdims=True), t=t)


def _state(dev):
    return [np.asarray(a) for a in (dev.preds, dev.pnorm, dev.masks,
                                    dev.acc, dev.S)]


# each step: the (client, model id) adds before one flush of both batches
_STEPS = {
    # light dirt everywhere plus a full client: two buckets
    "skewed_buckets": [[(c, 0) for c in range(N)]
                       + [(2, g) for g in range(1, CAP)],
                       [(0, 1), (1, 1), (1, 2), (3, 9), (3, 10)]],
    # three dirty clients pad the group count to four (repeating group
    # 0, which holds more than one row)
    "k_padding": [[(c, g) for c in (0, 1, 3) for g in range(3)],
                  [(0, 5), (0, 6), (2, 5), (3, 5)]],
    # three rows pad the slot width to four, five to eight
    "r_padding": [[(1, g) for g in range(3)], [(2, g) for g in range(5)]],
    # every client dirty at one width: the group gather is elided
    "all_clients": [[(c, g) for c in range(N) for g in range(2)],
                    [(c, g) for c in range(N) for g in range(2, 4)]],
    # a wide store's rows, then a narrow store's in the same block
    "narrow_after_wide": [[(1, g) for g in range(4)],
                          [(0, g) for g in range(4)],
                          [(3, 20), (1, 21)], [(2, 20), (0, 21)]],
    # more model ids than slots: evictions zero rows between flushes
    "churn": [[(c, g) for c in range(N) for g in range(CAP)],
              [(c, g) for c in (0, 1) for g in range(CAP, CAP + 3)],
              [(3, g) for g in range(2 * CAP, 2 * CAP + 6)]],
}


@pytest.mark.parametrize("steps", list(_STEPS), ids=list(_STEPS))
def test_compact_upload_matches_padded_block_bit_for_bit(steps):
    """After every flush the compact upload leaves preds, pnorm, masks,
    acc and S equal to those of the padded host block, bit for bit."""
    stores, rng = _mixed_stores(seed=14)
    dev, ref = DeviceStoreBatch(stores), _PaddedBlockBatch(stores)
    assert dev.v_max == 256 and stores[0].v_pad == 128
    for t, adds in enumerate(_STEPS[steps]):
        for c, gid in adds:
            _add(stores, rng, c, gid, t=float(t))
        assert dev.flush() == ref.flush() > 0
        for got, want in zip(_state(dev), _state(ref)):
            np.testing.assert_array_equal(got, want)
    assert dev.n_flushes == ref.n_flushes


def _aligned_empty(shape):
    """An uninitialised float32 array on a 4 KiB boundary: a CPU upload of
    it aliases host memory instead of copying."""
    n = int(np.prod(shape)) * 4
    raw = np.empty(n + 4096, np.uint8)
    off = (-raw.ctypes.data) % 4096
    return raw[off:off + n].view(np.float32).reshape(shape)


def test_staging_block_reuse_back_to_back_flushes(monkeypatch):
    """Flushes issued back to back, with nothing waited on between them,
    rewrite the same host staging block each time (a narrow store's rows
    where a wide store's were), while the device reads each upload only
    after a slow computation. The device must still end equal to a batch
    flushed once from the final stores, with zero V tails."""
    import jax

    import repro.core.device_store as ds
    x = jnp.ones((1024, 1024), jnp.float32)

    @jax.jit
    def late(rows, x):        # reads `rows` after a ~0.1 s product
        return rows * (1.0 + 0.0 * jnp.sum(jnp.tanh(x @ x @ x)))

    def slow_expand(rows, row_mask, src):
        return _expand(late(rows, x), row_mask, src)

    stores, rng = _mixed_stores(seed=15)
    dev = DeviceStoreBatch(stores)
    # blocks a CPU upload aliases: a rewrite under a pending read shows
    for u in (4, 8, 16):
        dev._stage[u] = _aligned_empty((u, dev.v_max, C))
    blocks = dict(dev._stage)
    monkeypatch.setattr(ds, "_expand", slow_expand)
    for r in range(6):
        for c in ((1, 3) if r % 2 == 0 else (0, 2)):    # wide, narrow
            for g in range(4):
                _add(stores, rng, c, 10 * r + g, t=float(r))
        dev.flush()
    monkeypatch.setattr(ds, "_expand", _expand)
    assert all(dev._stage[u] is blocks[u] for u in blocks)
    fresh = DeviceStoreBatch(stores)
    fresh.flush()
    for got, want in zip(_state(dev), _state(fresh)):
        np.testing.assert_array_equal(got, want)
    preds = np.asarray(dev.preds)
    for c, s in enumerate(stores):
        np.testing.assert_array_equal(preds[c, :, s.v_pad:], 0.0)


def _spans_after_selects(metrics):
    stores, rng = _fresh_stores(seed=4)
    eng = SelectionEngine(stores, CFG, ensemble_k=CFG.k, metrics=metrics)
    _churn(stores, rng)
    out = [eng.select([0, 2], t=1.0)]
    _churn(stores, rng, n_ops=10)
    out.append(eng.select(t=2.0))
    return out


def test_engine_select_records_the_span_tree():
    mx = Metrics()
    _spans_after_selects(mx)
    recs = mx.spans()
    assert {(r.name, r.parent) for r in recs} == {
        ("engine.select", None),
        ("engine.select.keys", "engine.select"),
        ("store.flush", "engine.select"),
        ("store.flush.drain", "store.flush"),
        ("store.flush.assemble", "store.flush"),
        ("store.flush.upload", "store.flush"),
        ("store.flush.launch", "store.flush"),
        ("engine.select.gather", "engine.select"),
        ("engine.select.launch", "engine.select"),
        ("engine.select.fetch", "engine.select"),
        ("engine.select.unpack", "engine.select")}
    assert [r.ids for r in recs if r.name == "engine.select"] == [
        {"select_id": 1}, {"select_id": 2}]
    for r in recs:
        assert r.start <= r.end
    # the host order of a select: PRNG keys, then the flush, then dispatch
    first = [r for r in recs if r.parent == "engine.select"][:6]
    assert [r.name for r in sorted(first, key=lambda r: r.start)] == [
        "engine.select.keys", "store.flush", "engine.select.gather",
        "engine.select.launch", "engine.select.fetch", "engine.select.unpack"]
    # a child lies inside its parent, and self times add up to it
    tot = mx.span_totals()
    assert all(s >= 0.0 for _, _, s in tot.values())
    for parent in ("engine.select", "store.flush"):
        kids = sum(t for name, (_, t, _) in tot.items()
                   if name.startswith(parent + ".")
                   or (parent == "engine.select" and name == "store.flush"))
        assert tot[parent][2] + kids == pytest.approx(tot[parent][1],
                                                      rel=1e-9, abs=1e-12)
    scalars = mx.frame().scalars
    assert scalars["engine.select{stat=count}"] == 2
    assert scalars["store.flush{stat=total_s}"] == tot["store.flush"][1]


def test_spans_change_no_result_and_the_null_registry_keeps_none():
    on = _spans_after_selects(Metrics())
    off = _spans_after_selects(NULL_METRICS)
    for a, b in zip(on, off):
        assert sorted(a) == sorted(b)
        for c in a:
            for k in ("chromosome", "val_accuracy", "pop", "objs",
                      "pareto_mask", "member_acc"):
                np.testing.assert_array_equal(a[c][k], b[c][k])
    assert NULL_METRICS.spans() == [] and NULL_METRICS.span_totals() == {}


def test_compiled_programs_carry_the_device_scopes():
    """The scopes a profiler trace is reduced by are in the optimized
    HLO's op names: a refactor that drops one fails here."""
    from repro.core.device_store import _gather
    stores, rng = _fresh_stores(seed=2)
    _churn(stores, rng)
    dev = DeviceStoreBatch(stores)
    K, R, U = 2, 4, 8
    rows = jnp.zeros((K * R, dev.v_max, C), jnp.float32)
    flush = _flush.lower(
        dev.preds, dev.pnorm, dev.masks, dev.acc, dev.S, dev.labels, dev.nv,
        rows, jnp.ones((K * R,), jnp.float32), jnp.arange(K, dtype=jnp.int32),
        jnp.zeros((K, R), jnp.int32)).compile().as_text()
    assert "/flush/scatter/" in flush and "/flush/gram/" in flush
    expand = _expand.lower(rows[:U], jnp.ones((U,), jnp.float32),
                           jnp.arange(K * R, dtype=jnp.int32) % U
                           ).compile().as_text()
    assert "/flush/scatter/" in expand
    idx = jnp.arange(2, dtype=jnp.int32)
    gather = _gather.lower(dev.preds, dev.labels, dev.masks, dev.acc, dev.S,
                           idx).compile().as_text()
    assert "/gather/" in gather
    preds, labels, masks, acc, S = dev.gather([0, 1])
    select = select_ensembles_from_stats.lower(
        acc, S, preds, labels, CFG, keys=client_keys(0, [0, 1]),
        model_mask=masks).compile().as_text()
    # the GA's scopes sit under `ga`, some inside its generation loop
    for sub in ("init", "eval", "rank", "breed", "survive"):
        assert re.search(rf'op_name="[^"]*/ga/([^"]*/)?{sub}/', select), sub
    assert "/vote/" in select
