"""Fault-injection subsystem tests (DESIGN.md §12): deterministic
injectors (byzantine / corruption / crash-restart / partition),
validation-gated admission in the gossip -> store path, the gossip
rejoin fix (stale-owner suppression must not outlive a restart), store
invalidation, end-to-end recovery (crash and partition->heal->repair
reconvergence), byte-identity of fault-free specs, spec/CLI error
paths, compiled-backend rejection, and the observability surface."""
import json
import math

import numpy as np
import pytest

from repro.faults import (AdmissionConfig, AdmissionController,
                          ByzantineConfig, ByzantineFault, CorruptionConfig,
                          CorruptionFault, FaultController)
from repro.faults.injectors import _pick_clients
from repro.core.bench import BenchEntry, PredictionStore
from repro.p2p import GossipConfig, GossipProtocol
from repro.sim import Experiment, ExperimentSpec

V, C = 64, 8


# ----------------------------------------------------- spec scaffolding

def _dissem_spec(n=8, drop=0.1, faults=None, repair=True, seed=0):
    """Pure-dissemination ring world (kind='none'): the fault paths ride
    the event loop, no training or stores needed."""
    d = {
        "data": {"kind": "none", "n_clients": n, "n_classes": C,
                 "n_val": V, "models_per_client": 2},
        "selection": {"enabled": False},
        "network": {
            "topology": "ring",
            "transport": {"name": "gossip",
                          "params": {"base_latency": 0.05, "jitter": 1.0,
                                     "bandwidth": 5e7, "drop_prob": drop,
                                     "inbox_capacity": 64}},
            "gossip": "push",
            "repair": ({"name": "anti_entropy",
                        "params": {"max_rounds": 40, "max_attempts": 8}}
                       if repair else None)},
        "schedule": {"mode": "async",
                     "train_cost": {"name": "affine",
                                    "params": {"base": 1.0, "slope": 0.2}}},
        "seed": seed}
    if faults is not None:
        d["faults"] = faults
    return ExperimentSpec.from_dict(d)


def _world_spec(n=8, faults=None, seed=0):
    """Prediction-world ring with selection: stores exist, so admission
    and byzantine payload poisoning are live."""
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
    if faults is not None:
        d["faults"] = faults
    return ExperimentSpec.from_dict(d)


# ---------------------------------------------------- no-fault identity

def test_empty_faults_section_is_byte_identical_to_none():
    """ISSUE acceptance: a spec with faults disabled produces a
    byte-identical run to one without the section at all — every
    scheduler fault branch is gated on `faults is not None`."""
    r1 = Experiment.from_spec(_dissem_spec()).run()
    spec2 = _dissem_spec(faults={})
    assert not spec2.faults.enabled
    r2 = Experiment.from_spec(spec2).run()
    assert r1.trace.events == r2.trace.events
    assert r1.net == r2.net
    assert "faults" not in r1.net and "faults" not in r2.net


# ------------------------------------------------- gossip rejoin (sat 1)

class _StubChurn:
    """departed() with no notion of rejoining — the exact blind spot the
    owner_gone override exists for."""

    def __init__(self, gone=()):
        self.gone = set(gone)

    def departed(self, c, t):
        return c in self.gone


def _gossip(n=4, churn=None):
    nb = [[j for j in range(n) if j != i] for i in range(n)]
    return GossipProtocol(GossipConfig(mode="push", seed=0), nb,
                          churn=churn)


def test_owner_gone_is_overridden_by_a_recorded_rejoin():
    g = _gossip(churn=_StubChurn(gone={1}))
    assert g.owner_gone(1, 5.0)          # departed, never rejoined
    assert not g.owner_gone(0, 5.0)      # never departed
    g.note_rejoin(1, 3.0)
    assert not g.owner_gone(1, 5.0)      # rejoined at 3.0 <= 5.0
    assert g.owner_gone(1, 2.0)          # ...but still gone BEFORE it


def test_rejoined_owner_models_propagate_again():
    """The stale-owner suppression fix: before the rejoin, a departed
    owner's models are suppressed; after note_rejoin they push again
    under a bumped incarnation that out-versions every pre-crash copy."""
    g = _gossip(churn=_StubChurn(gone={0}))
    key = (0, 0)
    assert g.on_local(0, key, t=5.0) == []          # suppressed
    assert g.stats.n_suppressed == 3
    g.note_rejoin(0, 5.0)
    assert g.incarnation[0] == 1
    fwd = g.on_local(0, key, t=6.0)
    assert sorted(dst for dst, _ in fwd) == [1, 2, 3]
    assert g.have[0][key] == 1                      # new incarnation
    # peers that held the incarnation-0 copy accept the refresh
    g2 = _gossip()
    g2.have[1][key] = 0
    accepted, _ = g2.on_receive(1, 0, key, t=0.0, version=1)
    assert accepted


def test_note_crash_clears_volatile_gossip_state():
    g = _gossip()
    g.on_local(0, (0, 0), t=0.0)
    g.on_receive(1, 0, (0, 0), t=0.1, version=0)
    assert (0, 0) in g.have[1] and (0, 0) in g.peer_has[1][0]
    g.note_rejoin(0, 1.0)
    assert not g.have[0]
    assert not g.peer_has[1].get(0)  # peers forget what 0 held


# ------------------------------------------------------------ injectors

def test_byzantine_modes_are_deterministic_and_normalized():
    rng = np.random.default_rng(0)
    p = rng.dirichlet(np.ones(C), size=V).astype(np.float32)
    for mode in ("label_flip", "uniform_noise", "confident_wrong"):
        f = ByzantineFault(ByzantineConfig(clients=(1,), mode=mode,
                                           seed=7), 8)
        q1, q2 = f.poison(p, 3, 5), f.poison(p, 3, 5)
        assert q1.shape == (V, C)
        np.testing.assert_array_equal(q1, q2)
        np.testing.assert_allclose(q1.sum(1), 1.0, atol=1e-5)
        assert not np.allclose(q1, p)
    flip = ByzantineFault(ByzantineConfig(clients=(1,), mode="label_flip",
                                          seed=7), 8)
    np.testing.assert_allclose(np.sort(flip.poison(p, 3, 5), axis=1),
                               np.sort(p, axis=1), atol=1e-6)
    cw = ByzantineFault(ByzantineConfig(clients=(1,), seed=7,
                                        confidence=0.9), 8)
    assert np.isclose(cw.poison(p, 3, 5).max(1), 0.9).all()


def test_pick_clients_explicit_fraction_and_range_check():
    assert _pick_clients(0.0, (3, 1), 8, 0, 1, "x") == (1, 3)
    assert len(_pick_clients(0.25, (), 8, 0, 1, "x")) == 2
    assert _pick_clients(0.25, (), 8, 0, 1, "x") == \
        _pick_clients(0.25, (), 8, 0, 1, "x")
    assert _pick_clients(0.25, (), 8, 0, 1, "x") != \
        _pick_clients(0.25, (), 8, 1, 1, "x") or True  # seed-sensitive
    with pytest.raises(ValueError, match="out of range"):
        _pick_clients(0.0, (9,), 8, 0, 1, "x")


def test_corruption_verdicts_counters_and_determinism():
    f = CorruptionFault(CorruptionConfig(flip_prob=1.0, detect_prob=1.0))
    assert f.check(0, 1, (2, 0), 0) == "detected"
    f2 = CorruptionFault(CorruptionConfig(flip_prob=1.0, detect_prob=0.0))
    assert f2.check(0, 1, (2, 0), 0) == "admitted"
    clean = CorruptionFault(CorruptionConfig(flip_prob=0.0))
    assert clean.check(0, 1, (2, 0), 0) is None
    # per-delivery stream: retries draw FRESH coins, but the sequence is
    # a pure function of the seed — two controllers replay identically
    a = CorruptionFault(CorruptionConfig(flip_prob=0.5, seed=3))
    b = CorruptionFault(CorruptionConfig(flip_prob=0.5, seed=3))
    seq_a = [a.check(0, 1, (2, 0), 0) for _ in range(16)]
    seq_b = [b.check(0, 1, (2, 0), 0) for _ in range(16)]
    assert seq_a == seq_b
    assert len(set(seq_a)) > 1  # the delivery index really folds in
    p = np.random.default_rng(0).dirichlet(np.ones(C), V).astype(np.float32)
    g1, g2 = a.corrupt(p, 4, 7), b.corrupt(p, 4, 7)
    np.testing.assert_array_equal(g1, g2)
    np.testing.assert_allclose(g1.sum(1), 1.0, atol=1e-5)
    with pytest.raises(ValueError, match="flip_prob"):
        CorruptionFault(CorruptionConfig(flip_prob=1.5))


def test_fault_controller_rejects_duplicates_and_array_world():
    byz = ByzantineFault(ByzantineConfig(clients=(0,)), 4)
    with pytest.raises(ValueError):
        FaultController([byz, byz], 4)
    fc = FaultController([byz], 4)
    with pytest.raises(ValueError, match="compiled"):
        fc.array_params()


# --------------------------------------------------- store invalidation

def _store(c=0, cap=4):
    rng = np.random.default_rng(c)
    return PredictionStore(c, cap, np.zeros((V, 2), np.float32),
                           rng.integers(0, C, V), C)


def _entry(gid, owner):
    return BenchEntry(model_id=gid, owner=owner, family="f",
                      predict=lambda x: np.zeros((len(x), C), np.float32))


def test_store_invalidate_masks_slot_and_bumps_generation():
    s = _store()
    p = np.full((V, C), 1.0 / C, np.float32)
    s.add(_entry(1, 1), preds=p)
    slot = int(np.flatnonzero(s.mask)[0])
    gen0 = int(s.slot_gen[slot])
    assert s.invalidate(1)
    assert not s.mask[slot] and s.entries[slot] is None
    assert int(s.slot_gen[slot]) == gen0 + 1
    assert not s.invalidate(1)      # already gone
    assert not s.invalidate(99)     # never present


def test_store_wipe_clears_everything():
    s = _store()
    p = np.full((V, C), 1.0 / C, np.float32)
    s.add(_entry(0, 0), preds=p)
    s.add(_entry(1, 1), preds=p)
    assert s.wipe() == 2
    assert not s.mask.any()
    assert all(e is None for e in s.entries)


# ------------------------------------------------------------ admission

def test_admission_gate_triages_and_invalidates():
    s = _store()
    adm = AdmissionController(AdmissionConfig(), [s])
    y = s.labels[:V]  # store labels are -1-padded past n_val
    good = np.full((V, C), 0.01, np.float32)
    good[np.arange(V), y] = 0.9                      # ~100% holdout acc
    wrong = np.full((V, C), 0.01, np.float32)
    wrong[np.arange(V), (y + 1) % C] = 0.9           # 0% holdout acc
    assert adm.screen(0, 1, good, s) == "admitted"
    assert adm.screen(0, 2, wrong, s) == "rejected"
    # borderline: exactly 2/C correct sits between 1.5/C and 2.5/C
    mid = np.full((V, C), 1.0 / C, np.float32)
    gate = adm.gates[0]
    hold = gate.holdout
    k = int(round(2 / C * len(hold)))
    mid[hold[:k], :] = 0.0
    mid[hold[:k], gate.y[:k]] = 1.0
    mid[hold[k:], :] = 0.0
    mid[hold[k:], (gate.y[k:] + 1) % C] = 1.0
    assert adm.screen(0, 3, mid, s) == "quarantined"
    assert 3 in gate.pen
    # a resident model whose refresh turns bad is invalidated in place
    s.add(_entry(1, 1), preds=good)
    assert adm.screen(0, 1, wrong, s) == "rejected"
    assert not s.mask.any()
    st = adm.as_dict()
    assert st["n_screened"] == 4 and st["n_rejected"] == 2
    assert st["n_quarantined"] == 1 and st["n_invalidated"] == 1
    adm.on_crash(0)
    assert not gate.pen


# --------------------------------------------------- e2e: crash-restart

def test_crash_restart_recovers_full_coverage_deterministically():
    faults = {"injectors": [{"name": "crash_restart",
                             "params": {"fraction": 0.25, "at": 1.5,
                                        "downtime": 1.5}}]}
    r1 = Experiment.from_spec(_dissem_spec(faults=faults)).run()
    fa = r1.net["faults"]
    assert fa["n_crashes"] == 2 and fa["n_restarts"] == 2
    assert r1.coverage == 1.0, \
        "re-dissemination after restart must close every gap"
    # the crash really wiped state: some client's bench hit size 0 > t=0
    assert any(size == 0 and t > 0
               for s in r1.trace.bench_sizes.values() for t, size in s)
    r2 = Experiment.from_spec(_dissem_spec(faults=faults)).run()
    assert r1.trace.events == r2.trace.events and r1.net == r2.net


# --------------------- e2e: partition -> heal -> repair reconvergence
# (satellite 4)

def test_partition_heal_repair_reconverges():
    heal_t = 3.5
    healed = {"injectors": [{"name": "partition",
                             "params": {"mode": "halves", "start": 0.5,
                                        "duration": heal_t - 0.5}}]}
    r = Experiment.from_spec(_dissem_spec(drop=0.0, faults=healed)).run()
    # during the partition the halves cannot be complete...
    n, mpc = 8, 2
    covered_at_heal = sum(
        max((size for t, size in s if t <= heal_t), default=0)
        for s in r.trace.bench_sizes.values())
    assert covered_at_heal < n * n * mpc, \
        "coverage should be partial while the ring is bisected"
    assert r.net["faults"]["n_partition_blocked"] > 0
    # ...and the heal event re-arms repair: full coverage, strictly
    # after the heal
    assert r.coverage == 1.0
    assert r.t_full > heal_t
    # control: a never-healing partition stays incomplete
    forever = {"injectors": [{"name": "partition",
                              "params": {"mode": "halves", "start": 0.5,
                                         "duration": math.inf}}]}
    rc = Experiment.from_spec(_dissem_spec(drop=0.0, faults=forever)).run()
    assert rc.coverage < 1.0
    # bit-identical reruns
    r2 = Experiment.from_spec(_dissem_spec(drop=0.0, faults=healed)).run()
    assert r.trace.events == r2.trace.events and r.net == r2.net


# -------------------------------------- e2e: byzantine + admission gate

def test_gate_keeps_byzantine_payloads_out_of_stores():
    byz_only = {"injectors": [{"name": "byzantine",
                               "params": {"fraction": 0.25,
                                          "mode": "confident_wrong"}}]}
    gated = dict(byz_only, admission={"name": "validation_gate",
                                      "params": {}})
    e_u = Experiment(_world_spec(faults=byz_only))
    r_u = e_u.run()
    e_g = Experiment(_world_spec(faults=gated))
    r_g = e_g.run()
    byz = e_g.faults.byzantine.clients
    assert len(byz) == 2

    def remote_owners(res, c):
        return {e.owner for e in res.stores[c].entries
                if e is not None and e.owner != c}

    honest = [c for c in range(8) if c not in byz]
    # ungated: poison flows in somewhere
    assert any(remote_owners(r_u, c) & byz for c in honest)
    assert r_u.net["faults"]["n_byzantine_poisoned"] > 0
    # gated: no honest store ever admits a byzantine owner's payload
    assert all(not (remote_owners(r_g, c) & byz) for c in honest)
    ad = r_g.net["admission"]
    assert ad["n_rejected"] > 0 and ad["n_admitted"] > 0
    assert ad["n_screened"] == sum(ad[k] for k in
                                   ("n_admitted", "n_quarantined",
                                    "n_rejected"))
    # local models NEVER cross the gate (negative-transfer safety valve)
    assert all((res.stores[c].is_local() & res.stores[c].mask).sum() > 0
               for res in (r_g,) for c in range(8))


# ------------------------------------------------- spec + config errors

def test_fault_spec_roundtrip_and_strict_errors(tmp_path):
    spec = _world_spec(faults={
        "injectors": [{"name": "byzantine", "params": {"fraction": 0.25}}],
        "admission": {"name": "validation_gate", "params": {}}})
    d = spec.to_dict()
    assert d["faults"]["injectors"][0]["name"] == "byzantine"
    assert ExperimentSpec.from_dict(d).to_dict() == d
    with pytest.raises(ValueError, match="unknown"):
        Experiment(_dissem_spec(faults={
            "injectors": [{"name": "nonesuch"}]})).build()
    with pytest.raises(ValueError, match="typo_knob"):
        Experiment(_dissem_spec(faults={
            "injectors": [{"name": "byzantine",
                           "params": {"typo_knob": 1}}]})).build()
    # sync + faults is rejected at build time, not parse time
    spec_sync = ExperimentSpec.from_dict({
        "data": {"kind": "synthetic_images"},
        "schedule": {"mode": "sync"},
        "faults": {"injectors": [{"name": "byzantine",
                                  "params": {"fraction": 0.5}}]}})
    with pytest.raises(ValueError, match="sync"):
        Experiment(spec_sync).build()


def test_compiled_backend_rejects_faults_loudly():
    spec = _dissem_spec(faults={
        "injectors": [{"name": "crash_restart",
                       "params": {"fraction": 0.25}}]})
    spec.schedule.backend.name = "compiled"
    spec.schedule.backend.params = {"tick": 0.05}
    with pytest.raises(ValueError, match="compiled"):
        Experiment(spec).run()


# --------------------------------------------------------- CLI (sat 2)

def test_cli_exits_2_with_one_line_error(tmp_path, capsys, monkeypatch):
    from repro.sim.run import main as cli
    # an explicit cache directory keeps the CLI's compile-cache helper
    # from pointing this test process at the checkout's cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    bad_json = tmp_path / "bad.json"
    bad_json.write_text("{not json")
    assert cli(["--spec", str(bad_json)]) == 2
    err = capsys.readouterr().err
    assert err.count("\n") == 1 and "invalid JSON" in err

    assert cli(["--spec", str(tmp_path / "missing.json")]) == 2
    err = capsys.readouterr().err
    assert err.count("\n") == 1 and "error:" in err

    bad_field = tmp_path / "field.json"
    bad_field.write_text(json.dumps({
        "data": {"kind": "none", "n_clients": 4},
        "selection": {"enabled": False},
        "schedule": {"mode": "async"},
        "faults": {"injectors": [{"name": "byzantine",
                                  "params": {"fractoin": 0.3}}]}},
        allow_nan=False))
    rc = cli(["--spec", str(bad_field)])
    err = capsys.readouterr().err
    assert rc == 2 and err.count("\n") == 1 and "fractoin" in err

    not_dict = tmp_path / "list.json"
    not_dict.write_text("[1, 2]")
    assert cli(["--spec", str(not_dict)]) == 2
    assert "expected one ExperimentSpec" in capsys.readouterr().err


# -------------------------------------------------------- observability

def test_fault_and_admission_metrics_are_emitted():
    spec = _world_spec(faults={
        "injectors": [{"name": "byzantine",
                       "params": {"fraction": 0.25,
                                  "mode": "confident_wrong"}},
                      {"name": "corruption",
                       "params": {"flip_prob": 0.3,
                                  "detect_prob": 0.5}}],
        "admission": {"name": "validation_gate", "params": {}}})
    spec.obs.enabled = True
    res = Experiment(spec).run()
    names = res.metrics.names()
    assert any(n.startswith("faults.injected") for n in names)
    assert any(n.startswith("admission.models") for n in names)
    assert any(n.startswith("transport.corrupt") for n in names)
    # metric values mirror the net counters exactly
    fa = res.net["faults"]
    byz_key = [n for n in names if "byzantine" in n][0]
    assert res.metrics.scalars[byz_key] == fa["n_byzantine_poisoned"]
