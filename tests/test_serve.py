"""Online serving subsystem tests (DESIGN.md §14): ServeSpec parsing
and strict errors, byte-identity of serve-free specs, deterministic
traffic/drift components, the monitor -> re-selection -> regret state
machine (driven directly), device-mirror coherence across validation
refreshes, the dormant DES path in core.dynamic (satellite: shapes,
determinism, and a hand-computable case where per-sample selection
beats the static vote), end-to-end drifted serving runs, and the
sync/compiled/storeless rejection paths."""
import numpy as np
import pytest

from repro.core.bench import BenchEntry, PredictionStore
from repro.core.device_store import DeviceStoreBatch
from repro.core.dynamic import (des_accuracy, dynamic_ensemble_predict,
                                knn_competence)
from repro.serve import (BurstyTraffic, BurstyTrafficConfig,
                         CovariateShiftDrift, CovariateShiftConfig,
                         LabelShiftDrift, LabelShiftConfig,
                         PoissonTraffic, PoissonTrafficConfig,
                         ServeConfig, ServingEngine)
from repro.sim import Experiment, ExperimentSpec

V, C = 64, 8


# ----------------------------------------------------- spec scaffolding

def _world_spec(n=8, serve=None, seed=0, **extra):
    d = {
        "data": {"kind": "prediction_world", "n_clients": n,
                 "n_classes": C, "n_val": V, "models_per_client": 2,
                 "quality_local": [0.6, 0.9],
                 "quality_remote": [0.5, 0.85]},
        "selection": {"enabled": True, "pop_size": 8, "generations": 2,
                      "k": 3},
        "network": {
            "topology": "ring",
            "transport": {"name": "gossip",
                          "params": {"base_latency": 0.05, "jitter": 1.0,
                                     "bandwidth": 5e7, "drop_prob": 0.1,
                                     "inbox_capacity": 64}},
            "gossip": "push",
            "repair": {"name": "anti_entropy",
                       "params": {"max_rounds": 40, "max_attempts": 8}}},
        "schedule": {"mode": "async",
                     "train_cost": {"name": "affine",
                                    "params": {"base": 1.0, "slope": 0.2}}},
        "seed": seed}
    d.update(extra)
    if serve is not None:
        d["serve"] = serve
    return ExperimentSpec.from_dict(d)


def _traffic(rate=40.0, batch=8, start=1.0, duration=5.0, **kw):
    p = {"rate": rate, "batch": batch, "start": start,
         "duration": duration}
    p.update(kw)
    return {"name": "poisson", "params": p}


# --------------------------------------------------- spec + error paths

def test_serve_spec_roundtrip_and_strict_errors():
    spec = _world_spec(serve={
        "traffic": _traffic(),
        "drift": [{"name": "label_shift",
                   "params": {"at": 3.0, "classes": [0, 1]}}],
        "window": 16, "threshold": 0.05})
    d = spec.to_dict()
    assert d["serve"]["traffic"]["name"] == "poisson"
    assert d["serve"]["drift"][0]["params"]["at"] == 3.0
    assert ExperimentSpec.from_dict(d).to_dict() == d
    with pytest.raises(ValueError, match="windoww"):
        _world_spec(serve={"traffic": _traffic(), "windoww": 9})
    with pytest.raises(ValueError, match="policy"):
        _world_spec(serve={"traffic": _traffic(), "policy": "oracle"})
    with pytest.raises(ValueError, match="drift without serve.traffic"):
        _world_spec(serve={"drift": [{"name": "label_shift"}]})
    # unknown component names / param typos fail at build, not run
    with pytest.raises(ValueError, match="unknown"):
        Experiment(_world_spec(serve={
            "traffic": {"name": "nonesuch"}})).build()
    with pytest.raises(ValueError, match="rtae"):
        Experiment(_world_spec(serve={
            "traffic": {"name": "poisson", "params": {"rtae": 9}}})).build()


def test_serveless_spec_is_byte_identical_to_empty_section():
    """ISSUE acceptance: a spec with an empty serve section produces a
    byte-identical run to one without the section at all — every
    scheduler serving branch is gated on `serving is not None`."""
    r1 = Experiment.from_spec(_world_spec()).run()
    spec2 = _world_spec(serve={})
    assert not spec2.serve.enabled
    r2 = Experiment.from_spec(spec2).run()
    assert r1.trace.events == r2.trace.events
    assert r1.net == r2.net
    assert "serve" not in (r1.net or {}) and "serve" not in (r2.net or {})


def test_serve_build_rejections():
    # no stores: dissemination-only world
    spec = ExperimentSpec.from_dict({
        "data": {"kind": "none", "n_clients": 4},
        "selection": {"enabled": False},
        "schedule": {"mode": "async"},
        "serve": {"traffic": _traffic()}})
    with pytest.raises(ValueError, match="builds none"):
        Experiment(spec).build()
    # no selection engine
    with pytest.raises(ValueError, match="selection.enabled"):
        Experiment(_world_spec(serve={"traffic": _traffic()},
                               selection={"enabled": False})).build()
    # monitor without the in-run select grid
    spec3 = _world_spec(serve={"traffic": _traffic()})
    spec3.schedule.select_during_run = False
    with pytest.raises(ValueError, match="select_during_run"):
        Experiment(spec3).build()
    # covariate shift needs real inputs
    with pytest.raises(ValueError, match="covariate_shift"):
        Experiment(_world_spec(serve={
            "traffic": _traffic(),
            "drift": [{"name": "covariate_shift",
                       "params": {"at": 2.0}}]})).build()
    # dynamic policy needs real query inputs too
    with pytest.raises(ValueError, match="dynamic"):
        Experiment(_world_spec(serve={
            "traffic": _traffic(), "policy": "dynamic"})).build()


def test_sync_and_compiled_reject_serving_loudly():
    spec = _world_spec(serve={"traffic": _traffic()})
    spec.schedule.mode = "sync"
    with pytest.raises(ValueError, match="sync"):
        Experiment(spec).build()
    spec2 = _world_spec(serve={"traffic": _traffic()})
    spec2.schedule.backend.name = "compiled"
    spec2.schedule.backend.params = {"tick": 0.05}
    with pytest.raises(ValueError, match="compiled"):
        Experiment(spec2).run()


# ------------------------------------------------------------- traffic

def dataclass_replace(cfg, **kw):
    import dataclasses
    return dataclasses.replace(cfg, **kw)


def test_poisson_traffic_is_deterministic_and_windowed():
    cfg = PoissonTrafficConfig(rate=50.0, batch=4, start=2.0,
                               duration=3.0, seed=11)
    tr = PoissonTraffic(cfg)
    ev1, ev2 = tr.events(6), tr.events(6)
    assert ev1 == ev2 and len(ev1) > 0
    assert ev1 == sorted(ev1)
    assert all(2.0 <= t < 5.0 and n == 4 and 0 <= c < 6
               for t, c, n in ev1)
    assert {c for _, c, _ in ev1} == set(range(6))  # fraction=1.0
    # seed-sensitive, client-keyed streams
    ev3 = PoissonTraffic(dataclass_replace(cfg, seed=12)).events(6)
    assert ev3 != ev1
    # explicit client subset
    sub = PoissonTraffic(dataclass_replace(cfg, clients=(1, 4))).events(6)
    assert {c for _, c, _ in sub} == {1, 4}
    # expected-count sanity: rate/batch batches/s * duration * clients
    expect = 50.0 / 4 * 3.0 * 6
    assert 0.5 * expect < len(ev1) < 1.5 * expect
    with pytest.raises(ValueError, match="rate"):
        PoissonTraffic(PoissonTrafficConfig(rate=0.0))
    with pytest.raises(ValueError, match="duration"):
        PoissonTraffic(PoissonTrafficConfig(duration=float("inf")))
    with pytest.raises(ValueError, match="out of range"):
        PoissonTraffic(PoissonTrafficConfig(clients=(9,))).events(4)


def test_bursty_traffic_thinning_modulates_rate():
    cfg = BurstyTrafficConfig(rate=80.0, batch=2, start=0.0,
                              duration=8.0, amp=1.0, period=8.0, seed=3)
    tr = BurstyTraffic(cfg)
    ev = tr.events(4)
    assert ev == tr.events(4) and ev == sorted(ev)
    assert all(0.0 <= t < 8.0 for t, _, _ in ev)
    # lam peaks in the first half-period and vanishes in the second:
    # sin >= 0 on [0, 4), sin <= 0 on [4, 8) with amp=1
    first = sum(1 for t, _, _ in ev if t < 4.0)
    second = len(ev) - first
    assert first > 3 * max(1, second)
    with pytest.raises(ValueError, match="amp"):
        BurstyTraffic(BurstyTrafficConfig(amp=1.5))
    with pytest.raises(ValueError, match="period"):
        BurstyTraffic(BurstyTrafficConfig(period=0.0))


# --------------------------------------------------------------- drift

def test_label_shift_weights_hand_math_and_errors():
    d = LabelShiftDrift(LabelShiftConfig(at=1.0, classes=(1, 3),
                                         skew=0.5))
    w = d.weights(4)
    # (1 - 0.5)/4 = 0.125 everywhere + 0.5/2 = 0.25 on classes {1, 3}
    np.testing.assert_allclose(w, [0.125, 0.375, 0.125, 0.375])
    assert np.isclose(w.sum(), 1.0)
    full = LabelShiftDrift(LabelShiftConfig(classes=(2,), skew=1.0))
    np.testing.assert_allclose(full.weights(4), [0, 0, 1, 0])
    assert d.clients_affected(8) == tuple(range(8))
    with pytest.raises(ValueError, match="out of range"):
        d.weights(2)
    with pytest.raises(ValueError, match="classes"):
        LabelShiftDrift(LabelShiftConfig(classes=()))
    with pytest.raises(ValueError, match="skew"):
        LabelShiftDrift(LabelShiftConfig(skew=1.5))
    with pytest.raises(ValueError, match="at"):
        LabelShiftDrift(LabelShiftConfig(at=-1.0))


def test_covariate_shift_transform_is_pure_and_composes():
    d = CovariateShiftDrift(CovariateShiftConfig(at=2.0, severity=0.5))
    x = np.linspace(0, 1, 12, dtype=np.float32).reshape(3, 4)
    y1, y2 = d.transform(x), d.transform(x)
    np.testing.assert_array_equal(y1, y2)          # pure, no rng
    np.testing.assert_allclose(y1, 0.5 * x + 0.5 * (1 - x), atol=1e-6)
    full = CovariateShiftDrift(CovariateShiftConfig(severity=1.0))
    np.testing.assert_allclose(full.transform(x), 1.0 - x, atol=1e-6)
    # severity=1 twice is the identity (inversion composed with itself)
    np.testing.assert_allclose(full.transform(full.transform(x)), x,
                               atol=1e-6)
    with pytest.raises(ValueError, match="severity"):
        CovariateShiftDrift(CovariateShiftConfig(severity=0.0))


# ------------------------------------- DES / core.dynamic (satellite 2)

def test_knn_competence_shapes_and_determinism():
    rng = np.random.default_rng(0)
    T, Vv, M = 10, 32, 5
    x_test = rng.random((T, 3, 4)).astype(np.float32)
    x_val = rng.random((Vv, 3, 4)).astype(np.float32)
    correct = (rng.random((M, Vv)) < 0.5).astype(np.float32)
    comp = np.asarray(knn_competence(x_test, x_val, correct, K=7))
    assert comp.shape == (T, M)
    assert (comp >= 0).all() and (comp <= 1).all()
    comp2 = np.asarray(knn_competence(x_test, x_val, correct, K=7))
    np.testing.assert_array_equal(comp, comp2)
    # K=V degenerates to each model's GLOBAL validation accuracy
    g = np.asarray(knn_competence(x_test, x_val, correct, K=Vv))
    np.testing.assert_allclose(g, np.tile(correct.mean(1), (T, 1)),
                               atol=1e-6)


def test_dynamic_selection_beats_static_vote_on_regional_experts():
    """Hand-built 2-model world: model A is perfect on the left half of
    the input line and wrong on the right, model B the mirror image. The
    static 2-model mean-prob vote is dominated by B's confidently-wrong
    probabilities on the left (and A's on the right), while KNORA's
    per-sample competence routes every query to its local expert."""
    xs = np.linspace(0.0, 1.0, 16, dtype=np.float32)[:, None]
    y = (xs[:, 0] > 0.5).astype(np.int32)       # class 1 on the right

    def probs_for(expert_left):
        p = np.zeros((16, 2), np.float32)
        for i, x in enumerate(xs[:, 0]):
            local = x <= 0.5 if expert_left else x > 0.5
            if local:                            # right, mildly
                p[i, y[i]] = 0.6
                p[i, 1 - y[i]] = 0.4
            else:                                # wrong, confidently
                p[i, 1 - y[i]] = 0.95
                p[i, y[i]] = 0.05
        return p

    probs = np.stack([probs_for(True), probs_for(False)])   # (M=2,16,2)
    correct = (probs.argmax(-1) == y[None, :]).astype(np.float32)
    # static mean-prob vote: the off-region expert's 0.95 overrules the
    # local expert's 0.6 everywhere
    static = probs.mean(0).argmax(-1)
    assert (static == y).mean() == 0.0
    # DES with k=1: nearest-neighbour competence picks the local expert
    comp = np.asarray(knn_competence(xs, xs, correct, K=3))
    pred = np.asarray(dynamic_ensemble_predict(probs, comp, k=1))
    assert (pred == y).mean() == 1.0
    acc = float(des_accuracy(xs, y, xs, y, probs, probs, K=3, k=1))
    assert acc == 1.0


# --------------------------- monitor / regret unit (engine driven raw)

class _StubStore:
    def __init__(self, labels, preds):
        self.n_val = len(labels)
        self.labels = np.asarray(labels, np.int32)
        self.preds = np.asarray(preds, np.float32)
        self.mask = np.ones(len(preds), bool)
        self.x_val = np.zeros((len(labels), 2), np.float32)


class _StubEngine:
    ensemble_k = 2

    def __init__(self, chrom):
        self.chrom = np.asarray(chrom, np.float32)

    def chromosome(self, c):
        return self.chrom


class _NullTraffic:
    kind = "null"

    def events(self, n):
        return []


def _monitor_engine(window=8, threshold=0.2, debounce=0.5):
    labels = np.arange(V) % C
    good = np.zeros((V, C), np.float32)
    good[np.arange(V), labels] = 1.0            # model 0: always right
    bad = np.zeros((V, C), np.float32)
    bad[np.arange(V), (labels + 1) % C] = 1.0   # model 1: always wrong
    store = _StubStore(labels, np.stack([good, bad]))
    eng = _StubEngine([1.0, 0.0])
    cfg = ServeConfig(window=window, threshold=threshold,
                      debounce=debounce, seed=5)
    return ServingEngine(cfg, _NullTraffic(), [], 1, C, [store], eng), eng


def test_monitor_triggers_once_then_debounces_and_resets():
    sv, eng = _monitor_engine()
    # warm the window on the good ensemble: full accuracy, no trigger
    for b in range(3):
        assert not sv.on_query(0, 0.1 * (b + 1), b, 4)
    assert sv._final_window[0] == 1.0
    # degrade: the engine now serves the always-wrong model
    eng.chrom = np.asarray([0.0, 1.0], np.float32)
    fired = [sv.on_query(0, 1.0 + 0.1 * b, 3 + b, 4) for b in range(4)]
    assert fired.count(True) == 1               # breach fires exactly once
    assert sv.stats.n_reselections == 1
    assert 0 in sv._frozen                      # shadow arm snapshotted
    # within the debounce interval nothing re-fires even while breached
    assert not sv.on_query(0, 1.45, 7, 4)
    # re-selection landed: window + peak reset, the recovered ensemble
    # is judged on its own record
    eng.chrom = np.asarray([1.0, 0.0], np.float32)
    sv.note_selected([0], 2.0)
    assert len(sv._window[0]) == 0 and 0 not in sv._peak
    for b in range(3):
        assert not sv.on_query(0, 2.0 + 0.2 * (b + 1), 8 + b, 4)
    # regret: live (perfect) vs frozen (always-wrong) integrates > 0
    assert sv.stats.regret > 0
    d = sv.stats_dict()
    assert d["n_batches"] == 11 and d["n_reselections"] == 1
    assert d["window_acc"] == 1.0 and d["regret"] > 0
    assert d["latency_p50"] > 0 and d["latency_p99"] >= d["latency_p50"]
    sv.note_dropped(0, 3)
    assert sv.stats.n_dropped == 3


def test_serving_engine_rejects_bad_configs_and_array_world():
    labels = np.arange(V) % C
    store = _StubStore(labels, np.zeros((2, V, C), np.float32))
    eng = _StubEngine([1.0, 0.0])
    with pytest.raises(ValueError, match="window"):
        ServingEngine(ServeConfig(window=0), _NullTraffic(), [], 1, C,
                      [store], eng)
    with pytest.raises(ValueError, match="dynamic"):
        ServingEngine(ServeConfig(policy="dynamic"), _NullTraffic(), [],
                      1, C, [store], eng, query_pools=None)
    sv = ServingEngine(ServeConfig(), _NullTraffic(), [], 1, C,
                       [store], eng)
    with pytest.raises(ValueError, match="compiled"):
        sv.array_params()


# ------------------------------- device mirror coherence after refresh

def test_device_refresh_labels_matches_fresh_rebuild():
    """After a validation refresh (drift resample), flushing the marked
    device mirror must be bit-identical to rebuilding a fresh
    DeviceStoreBatch over the mutated stores."""
    rng = np.random.default_rng(7)
    cap = 4
    stores = []
    for c in range(3):
        s = PredictionStore(c, cap, np.zeros((V, 2), np.float32),
                            rng.integers(0, C, V), C)
        for m in range(3):
            p = rng.random((V, C)).astype(np.float32)
            s.add(BenchEntry(model_id=m, owner=c, family="f",
                             predict=lambda x: None),
                  preds=p / p.sum(1, keepdims=True))
        stores.append(s)
    dev = DeviceStoreBatch(stores)
    dev.flush()
    # drift hits client 1: resample its validation rows
    s = stores[1]
    ridx = rng.permutation(V)
    s.refresh_validation(s.x_val, np.asarray(s.labels[:V])[ridx],
                         np.asarray(s.preds[:, :V])[:, ridx])
    dev.refresh_labels(1)
    dev.flush()
    fresh = DeviceStoreBatch(stores)
    fresh.flush()
    np.testing.assert_array_equal(np.asarray(dev.preds),
                                  np.asarray(fresh.preds))
    np.testing.assert_array_equal(np.asarray(dev.labels),
                                  np.asarray(fresh.labels))
    np.testing.assert_array_equal(np.asarray(dev.acc),
                                  np.asarray(fresh.acc))
    np.testing.assert_array_equal(np.asarray(dev.S),
                                  np.asarray(fresh.S))


# ------------------------------------------------- e2e: drifted serving

def _drift_spec(seed=0, monitor=True, threshold=0.08):
    return _world_spec(seed=seed, serve={
        "traffic": _traffic(rate=40.0, batch=8, start=1.0, duration=6.0),
        "drift": [{"name": "label_shift",
                   "params": {"at": 4.0, "classes": [0, 1],
                              "skew": 1.0}}],
        "monitor": monitor, "window": 32, "threshold": threshold,
        "debounce": 0.5})


def test_e2e_serve_with_drift_is_deterministic_and_monitored():
    # In this world the flip costs one client's 32-query window two more
    # misses than at its peak (0.0625): a threshold under that makes the
    # flip itself breach. At 0.08 only a dip before the flip could fire,
    # which hangs on which ensembles the GA happened to pick.
    spec = lambda **kw: _drift_spec(threshold=0.05, **kw)  # noqa: E731
    r1 = Experiment.from_spec(spec()).run()
    sv = r1.net["serve"]
    assert sv["n_queries"] > 500 and sv["n_batches"] > 50
    assert sv["n_drift_events"] == 1
    assert sv["n_reselections"] >= 1, \
        "the label flip must breach the window threshold"
    assert sv["latency_p50"] > 0 and sv["latency_p99"] >= sv["latency_p50"]
    assert 0.0 <= sv["window_acc"] <= 1.0
    # bit-identical reruns: serving is a pure function of the spec
    r2 = Experiment.from_spec(spec()).run()
    assert r1.trace.events == r2.trace.events and r1.net == r2.net
    # the frozen control serves the same traffic but never re-selects
    rf = Experiment.from_spec(spec(monitor=False)).run()
    svf = rf.net["serve"]
    assert svf["n_reselections"] == 0
    assert svf["n_queries"] == sv["n_queries"], \
        "traffic schedules are monitor-independent"


def test_serve_metrics_are_emitted():
    spec = _drift_spec()
    spec.obs.enabled = True
    res = Experiment(spec).run()
    names = res.metrics.names()
    assert any(n.startswith("serve.queries") for n in names)
    assert any(n.startswith("serve.reselections") for n in names)
    assert any(n.startswith("serve.window_acc") for n in names)
    sv = res.net["serve"]
    served = [n for n in names
              if n.startswith("serve.queries") and "served" in n][0]
    assert res.metrics.scalars[served] == sv["n_queries"]
