"""Parity and contract tests for the compiled array-world backend.

The event loop (`fl.scheduler.simulate_async`) is the golden reference;
`repro.sim.compiled` must reproduce its dissemination metrics (DESIGN.md
§10). Three tiers, each over a grid that was validated exhaustively when
these tolerances were set:

  T1 deterministic (drop=0, jitter=0, no churn/repair): EXACT — every
     net counter equal, coverage 1.0 on both, |t_full delta| <= tick.
  T2 lossy + anti-entropy repair: both backends reach coverage 1.0;
     bytes and t_full agree within a documented tolerance (the in-scan
     hash streams are a different realization of the same drop/jitter
     distributions than the event loop's per-edge numpy streams).
  T3 churn: coverage and accepted counts agree within tolerance.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.sim.experiment import Experiment
from repro.sim.spec import ExperimentSpec

REPAIR = {"interval": 0.5, "start": 0.5, "max_rounds": 40}
CHURN = {"availability_beta": 0.3, "window": 0.5, "join_spread": 1.0}


def _spec(backend, topo, n, mpc=1, seed=0, drop=0.0, churn=None,
          repair=None, backend_params=None, kind="none", gossip="push",
          selection=None, mode="async", select_during_run=False):
    net = {"topology": topo, "topology_k": 4,
           "transport": {"name": "gossip",
                         "params": {"base_latency": 0.05, "jitter": 0.0,
                                    "drop_prob": drop}},
           "gossip": gossip}
    if churn is not None:
        net["churn"] = {"name": "lognormal", "params": churn}
    if repair is not None:
        net["repair"] = {"name": "anti_entropy", "params": repair}
    return ExperimentSpec.from_dict({
        "data": {"kind": kind, "n_clients": n, "models_per_client": mpc,
                 "n_val": 16, "n_classes": 4},
        "selection": selection or {"enabled": False},
        "network": net,
        "schedule": {"mode": mode,
                     "select_during_run": select_during_run,
                     "backend": {"name": backend,
                                 "params": backend_params or {}}},
        "seed": seed})


def _pair(topo, n, mpc, seed, tick, **kw):
    ev = Experiment.from_spec(_spec("event", topo, n, mpc, seed,
                                    **kw)).run()
    co = Experiment.from_spec(_spec(
        "compiled", topo, n, mpc, seed,
        backend_params={"tick": tick}, **kw)).run()
    return ev, co


# ---- T1: deterministic tier is exact ----------------------------------


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(["full", "ring", "small_world"]),
       st.sampled_from([5, 8, 16, 32]), st.sampled_from([1, 2]),
       st.integers(0, 4), st.sampled_from([0.05, 0.025]))
def test_deterministic_parity_exact(topo, n, mpc, seed, tick):
    ev, co = _pair(topo, n, mpc, seed, tick)
    assert co.net == ev.net
    assert ev.coverage == co.coverage == 1.0
    assert abs(ev.t_full - co.t_full) <= tick + 1e-9


# ---- T2: lossy links + repair converge with comparable cost -----------


@settings(max_examples=10, deadline=None)
@given(st.sampled_from(["ring", "small_world"]),
       st.sampled_from([16, 32]), st.integers(0, 4))
def test_lossy_repair_parity(topo, n, seed):
    ev, co = _pair(topo, n, 1, seed, 0.05, drop=0.1, repair=REPAIR)
    assert ev.coverage == 1.0 and co.coverage == 1.0
    b_ev = ev.net["transport"]["bytes_sent"]
    b_co = co.net["transport"]["bytes_sent"]
    assert abs(b_co - b_ev) <= 0.25 * b_ev
    assert abs(co.t_full - ev.t_full) <= 0.5 * ev.t_full


# ---- T3: churn reshapes the reachable set comparably ------------------


@settings(max_examples=10, deadline=None)
@given(st.sampled_from(["full", "ring"]), st.sampled_from([16, 32]),
       st.integers(0, 4))
def test_churn_parity(topo, n, seed):
    ev, co = _pair(topo, n, 1, seed, 0.05, drop=0.1, churn=CHURN,
                   repair=REPAIR)
    assert abs(co.coverage - ev.coverage) <= 0.2
    a_ev = ev.net["gossip"]["n_accepted"]
    a_co = co.net["gossip"]["n_accepted"]
    if a_ev:
        assert abs(a_co - a_ev) <= 0.25 * a_ev


# ---- deterministic contracts ------------------------------------------


def test_key_block_sharding_equivalent():
    base = Experiment.from_spec(_spec(
        "compiled", "ring", 8, 2, 0,
        backend_params={"tick": 0.05})).run()
    shard = Experiment.from_spec(_spec(
        "compiled", "ring", 8, 2, 0,
        backend_params={"tick": 0.05, "key_block": 5})).run()
    assert shard.net == base.net
    assert shard.t_full == base.t_full
    assert shard.coverage == base.coverage


def test_compiled_rerun_is_deterministic():
    a = Experiment.from_spec(_spec("compiled", "small_world", 16, 2, 3,
                                   drop=0.2, repair=REPAIR)).run()
    b = Experiment.from_spec(_spec("compiled", "small_world", 16, 2, 3,
                                   drop=0.2, repair=REPAIR)).run()
    assert a.net == b.net and a.t_full == b.t_full


def test_perf_counters_both_backends():
    ev, co = _pair("ring", 8, 1, 0, 0.05)
    assert ev.perf["backend"] == "event"
    assert co.perf["backend"] == "compiled"
    for r in (ev, co):
        assert r.perf["wall_s"] >= 0
        assert set(r.perf["phases"])  # at least one phase timing
        assert r.summary()["perf"] == r.perf
    assert co.perf["n_ticks"] > 0


def test_prediction_world_store_parity():
    kw = dict(kind="prediction_world",
              selection={"enabled": True}, select_during_run=False)
    ev, co = _pair("ring", 6, 2, 1, 0.05, **kw)
    assert ev.coverage == co.coverage == 1.0
    for s_ev, s_co in zip(ev.stores, co.stores):
        assert {e.model_id for e in s_ev.entries} == \
            {e.model_id for e in s_co.entries}


def test_compiled_rejects_image_worlds():
    spec = _spec("compiled", "ring", 4, kind="synthetic_images")
    with pytest.raises(ValueError, match="image worlds"):
        Experiment.from_spec(spec).run()


def test_compiled_rejects_in_run_selection():
    spec = _spec("compiled", "ring", 4, kind="prediction_world",
                 selection={"enabled": True}, select_during_run=True)
    with pytest.raises(ValueError, match="in-loop selection"):
        Experiment.from_spec(spec).run()


def test_sync_mode_rejects_compiled_backend():
    spec = ExperimentSpec.from_dict({
        "data": {"kind": "synthetic_images", "n_clients": 4,
                 "n_samples": 160, "n_classes": 4, "image_size": 6},
        "schedule": {"mode": "sync", "backend": "compiled"},
        "seed": 0})
    with pytest.raises(ValueError, match="async"):
        Experiment.from_spec(spec).build()


def test_compiled_rejects_push_pull():
    with pytest.raises(ValueError, match="push"):
        Experiment.from_spec(_spec("compiled", "ring", 4,
                                   gossip="push_pull")).run()


def test_compiled_rejects_bounded_inboxes():
    spec = _spec("compiled", "ring", 4)
    spec.network.transport.params["inbox_capacity"] = 2
    with pytest.raises(ValueError, match="inbox"):
        Experiment.from_spec(spec).run()


def test_compiled_rejects_repair_with_partial_key_block():
    spec = _spec("compiled", "ring", 8, mpc=2, repair=REPAIR,
                 backend_params={"tick": 0.05, "key_block": 5})
    with pytest.raises(ValueError, match="key_block"):
        Experiment.from_spec(spec).run()


def test_unknown_backend_params_fail_loudly():
    with pytest.raises(ValueError, match="nope"):
        Experiment.from_spec(_spec("compiled", "ring", 4,
                                   backend_params={"nope": 1})).run()
