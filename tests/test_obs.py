"""Observability tests (DESIGN.md §11).

Three load-bearing guarantees:

  1. TRUE NO-OP: an obs-disabled run is bit-identical to one that never
     heard of observability — same events, same net counters, same
     selections (the golden-trace tier's protection extends to this PR).
  2. BACKEND PARITY: the event loop and the compiled array world emit
     the SAME metric names, with exactly equal scalar values on the
     deterministic tier (drop=0, jitter=0, no churn) — the one
     tolerance is `coverage.t_full` (tick quantization, <= one tick).
  3. STRICT JSON: every serialized artifact (metrics frame, trace,
     summary) parses under a strict JSON reader — NaN (e.g. t_full on a
     never-complete run) becomes null, never a bare ``NaN`` token.
"""
import json
import math

import numpy as np
import pytest

from repro.obs import (Metrics, MetricsFrame, NULL_METRICS, Obs,
                       TraceCollector, export_chrome_trace, json_ready,
                       metric_key)
from repro.sim import Experiment, ExperimentSpec

TICK = 0.05


def _reject_nan(s):
    raise ValueError(f"non-strict JSON token {s!r}")


def strict_loads(s: str):
    """json.loads that rejects NaN/Infinity/-Infinity tokens."""
    return json.loads(s, parse_constant=_reject_nan)


def base_spec(backend="event", obs=None, drop=0.0, n=10, seed=3,
              repair=False):
    d = {
        "data": {"kind": "none", "n_clients": n, "models_per_client": 2},
        "selection": {"enabled": False},
        "network": {
            "topology": "ring",
            "transport": {"name": "gossip",
                          "params": {"base_latency": 0.05, "jitter": 0.0,
                                     "drop_prob": drop}},
            "gossip": "push"},
        "schedule": {"mode": "async", "select_during_run": False,
                     "backend": backend},
        "seed": seed,
    }
    if repair:
        d["network"]["repair"] = {"name": "anti_entropy",
                                  "params": {"max_rounds": 30,
                                             "max_attempts": 6}}
    if obs is not None:
        d["obs"] = obs
    return ExperimentSpec.from_dict(d)


def run(spec):
    return Experiment.from_spec(spec).run()


# ---- registry unit ----------------------------------------------------

def test_metric_key_sorts_labels():
    assert metric_key("net.bytes") == "net.bytes"
    assert metric_key("net.bytes", {"kind": "digest", "a": 1}) == \
        "net.bytes{a=1,kind=digest}"


def test_counter_gauge_series():
    mx = Metrics(resolution=0.5)
    mx.inc("c", 2, t=0.0)
    mx.inc("c", 3, t=1.0)
    mx.set("g", 7.5)
    mx.observe("s", 1.0, t=0.0)
    mx.observe("s", 4.0, t=0.1)   # same bucket: last write wins
    mx.observe("s", 9.0, t=2.0)
    fr = mx.frame(meta={"seed": 0})
    assert fr.scalars["c"] == 5
    assert fr.scalars["g"] == 7.5
    assert fr.series["c"] == [[0.0, 2.0], [1.0, 5.0]]
    assert fr.series["s"] == [[0.0, 4.0], [2.0, 9.0]]
    assert fr.names() == {"c", "g", "s"}


def test_kind_mismatch_rejected():
    mx = Metrics()
    mx.inc("x", 1)
    with pytest.raises(ValueError, match="already registered as counter"):
        mx.set("x", 2.0)


def test_disabled_metrics_are_inert():
    mx = Metrics(enabled=False)
    mx.inc("c", 5, t=1.0)
    mx.set("g", 1.0)
    mx.observe("s", 2.0, t=0.0)
    fr = mx.frame()
    assert fr.scalars == {} and fr.series == {}
    assert NULL_METRICS.frame().names() == set()


def test_stopwatch_accumulates_and_records():
    mx = Metrics()
    sw = mx.stopwatch("w")
    with sw(t=0.5):
        pass
    with sw(t=1.5):
        pass
    assert sw.laps == 2 and sw.total >= 0.0
    assert len(mx.frame().series["w"]) == 2


def test_span_buffer_is_bounded_and_totals_count_every_span(monkeypatch):
    import repro.obs.metrics as obs_metrics
    monkeypatch.setattr(obs_metrics, "SPAN_BUFFER", 3)
    mx = Metrics()
    for i in range(5):
        with mx.span("outer", i=i):
            with mx.span("inner"):
                pass
    recs = mx.spans()
    assert len(recs) == 3                      # the last three to close
    assert recs[-1].name == "outer" and recs[-1].ids == {"i": 4}
    assert recs[-2].parent == "outer"
    n, total, self_s = mx.span_totals()["outer"]
    assert n == 5 and 0.0 <= self_s <= total
    scalars = mx.frame().scalars
    assert scalars["outer{stat=count}"] == 5
    assert scalars["inner{stat=count}"] == 5
    assert scalars["outer{stat=self_s}"] == self_s
    # the disabled registry allocates no span and records nothing
    assert NULL_METRICS.span("outer") is NULL_METRICS.span("other", i=1)
    with NULL_METRICS.span("outer"):
        pass
    assert NULL_METRICS.frame().names() == set()


def test_frame_json_roundtrip():
    mx = Metrics()
    # replint: ok[OBS-PARITY] fixture name for the roundtrip test, not a real series
    mx.inc("net.bytes", 10, t=0.0, kind="model")
    mx.set("coverage.t_full", float("nan"))
    fr = mx.frame(meta={"seed": 1})
    s = json.dumps(fr.to_dict(), allow_nan=False)  # must not raise
    fr2 = MetricsFrame.from_dict(strict_loads(s))
    assert fr2.names() == fr.names()
    assert fr2.scalars["net.bytes{kind=model}"] == 10
    assert fr2.scalars["coverage.t_full"] is None   # NaN -> null
    assert fr2.series == {k: v for k, v in fr.series.items()}


def test_json_ready_nan_and_numpy():
    out = json_ready({"a": float("nan"), "b": np.float32(2.5),
                      "c": (1, np.inf), "d": np.arange(3)})
    assert out == {"a": None, "b": 2.5, "c": [1, None], "d": [0, 1, 2]}


# ---- satellite 1: strict JSON end-to-end ------------------------------

def test_summary_nan_t_full_serializes_null():
    # drop everything: dissemination can never complete -> t_full = NaN
    spec = base_spec(obs={"enabled": True}, drop=1.0, n=6)
    res = run(spec)
    assert res.coverage < 1.0 and math.isnan(res.t_full)
    s = json.dumps(res.summary(), allow_nan=False)  # strict: no bare NaN
    d = strict_loads(s)
    assert d["t_full"] is None
    # the metrics frame carries the same null
    m = strict_loads(json.dumps(res.metrics.to_dict(), allow_nan=False))
    assert m["scalars"]["coverage.t_full"] is None


# ---- guarantee 1: obs-disabled is bit-identical -----------------------

def test_obs_disabled_bit_identical():
    a = run(base_spec(drop=0.3, repair=True))           # no obs section
    b = run(base_spec(obs={"enabled": True, "trace": True},
                      drop=0.3, repair=True))           # fully enabled
    assert a.trace.events == b.trace.events
    assert a.net == b.net
    assert a.coverage == b.coverage and a.t_full == b.t_full
    assert a.metrics is None and b.metrics is not None


def test_perf_keys_bit_compatible():
    res = run(base_spec())
    assert set(res.perf) == {"backend", "wall_s", "n_events",
                             "events_per_s", "phases"}
    assert set(res.perf["phases"]) == {"net_s", "select_s"}
    assert res.perf["backend"] == "event"


# ---- guarantee 2: event vs compiled metric-frame parity ---------------

@pytest.mark.parametrize("n,seed", [(10, 3), (16, 7)])
def test_backend_metric_frame_parity(n, seed):
    ev = run(base_spec("event", obs={"enabled": True}, n=n, seed=seed))
    co = run(base_spec("compiled", obs={"enabled": True}, n=n, seed=seed))
    fe, fc = ev.metrics, co.metrics
    # identical metric NAME sets (scalars and series alike)
    assert fe.names() == fc.names()
    assert set(fe.series) == set(fc.series)
    # exactly equal scalar values, except t_full (tick quantization)
    for k in fe.scalars:
        if k == "coverage.t_full":
            assert abs(fe.scalars[k] - fc.scalars[k]) <= TICK + 1e-9
        else:
            assert fe.scalars[k] == fc.scalars[k], k
    # both series sets end at the same cumulative totals
    for k in ("net.msgs_on_wire", "net.bytes_on_wire", "gossip.accepted"):
        assert fe.series[k][-1][1] == fc.series[k][-1][1], k
    assert fe.meta["backend"] == "event"
    assert fc.meta["backend"] == "compiled"


# ---- trace export -----------------------------------------------------

def test_trace_collector_and_export_schema():
    tc = TraceCollector()
    tc.slice(0, "train m0", 0.0, 1.0, cat="train")
    tc.slice(1, "recv (0,0)", 1.5, 1.5, cat="recv")
    tc.flow(0, 1, "(0,0)", 1.0, 1.5)
    tc.counter("coverage", 1.5, 0.25)
    doc = export_chrome_trace(tc, n_clients=2, meta={"seed": 0})
    strict_loads(json.dumps(doc, allow_nan=False))
    evs = doc["traceEvents"]
    phs = [e["ph"] for e in evs]
    assert phs.count("X") == 3          # 2 slices + 1 flow send anchor
    assert phs.count("s") == 1 and phs.count("f") == 1
    assert phs.count("C") == 1
    # every event targets a metadata-named track
    named = {e["tid"] for e in evs if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert {e["tid"] for e in evs if e["ph"] in "Xsf"} <= named
    # ts scaling: virtual seconds -> microseconds
    tr = [e for e in evs if e["ph"] == "X" and e["name"] == "train m0"][0]
    assert tr["ts"] == 0.0 and tr["dur"] == 1e6
    # flow ends pair by id, s on source track, f on destination track
    s = [e for e in evs if e["ph"] == "s"][0]
    f = [e for e in evs if e["ph"] == "f"][0]
    assert s["id"] == f["id"] and s["tid"] == 1 and f["tid"] == 2
    assert f["bp"] == "e"


def test_end_to_end_trace_run(tmp_path):
    mp, tp = tmp_path / "m.json", tmp_path / "t.json"
    spec = base_spec(obs={
        "enabled": True, "trace": True,
        "sinks": [{"name": "metrics_json", "params": {"path": str(mp)}},
                  {"name": "perfetto", "params": {"path": str(tp)}}]},
        drop=0.2, repair=True)
    res = run(spec)
    doc = strict_loads(tp.read_text())
    evs = doc["traceEvents"]
    kinds = {e["name"].split(" ")[0] for e in evs if e["ph"] == "X"}
    assert {"train", "recv", "send", "digest_send"} <= kinds
    # one flow pair per in-flight message, ids match 1:1
    assert {e["id"] for e in evs if e["ph"] == "s"} == \
        {e["id"] for e in evs if e["ph"] == "f"}
    assert {"bytes_on_wire", "coverage"} <= \
        {e["name"] for e in evs if e["ph"] == "C"}
    fr = MetricsFrame.from_dict(strict_loads(mp.read_text()))
    assert fr.scalars == json_ready(res.metrics.to_dict()["scalars"])


# ---- spec-level validation --------------------------------------------

def test_obs_spec_roundtrip():
    spec = base_spec(obs={"enabled": True, "trace": True,
                          "resolution": 0.1,
                          "sinks": [{"name": "metrics_json",
                                     "params": {"path": "m.json"}}]})
    assert ExperimentSpec.from_dict(spec.to_dict()) == spec
    assert ExperimentSpec.from_json(spec.to_json()) == spec


def test_trace_on_compiled_rejected():
    spec = base_spec("compiled", obs={"enabled": True, "trace": True})
    with pytest.raises(ValueError, match="backend='event'"):
        Experiment.from_spec(spec).build()


def test_sinks_without_obs_rejected():
    spec = base_spec(obs={"enabled": False,
                          "sinks": [{"name": "metrics_json"}]})
    with pytest.raises(ValueError, match="obs.enabled is false"):
        Experiment.from_spec(spec).build()


def test_unknown_sink_rejected():
    spec = base_spec(obs={"enabled": True,
                          "sinks": [{"name": "nope"}]})
    with pytest.raises(ValueError, match="unknown sink"):
        Experiment.from_spec(spec).build()


def test_engine_metrics_series():
    # in-run selection over a prediction world: engine probes fire
    spec = ExperimentSpec.from_dict({
        "data": {"kind": "prediction_world", "n_clients": 6,
                 "n_classes": 4, "n_val": 32, "models_per_client": 2},
        "selection": {"pop_size": 8, "generations": 2, "k": 3},
        "network": {"topology": "ring",
                    "transport": {"name": "gossip",
                                  "params": {"base_latency": 0.05,
                                             "jitter": 0.0,
                                             "drop_prob": 0.0,
                                             "sizer": {
                                                 "name":
                                                     "prediction_matrix",
                                                 "params": {
                                                     "n_val": 32,
                                                     "n_classes": 4}}}},
                    "gossip": "push"},
        "schedule": {"mode": "async"},
        "obs": {"enabled": True},
        "seed": 1})
    res = run(spec)
    names = res.metrics.names()
    for k in ("engine.ga_batch_width", "store.flush{stat=total_s}",
              "engine.flush_dirty_slots", "engine.select_batch_width",
              "engine.select_wall_s"):
        assert k in names, k
    # the stopwatch-derived perf split matches the recorded laps
    sel = sum(v for _, v in res.metrics.series["engine.select_wall_s"])
    assert res.perf["phases"]["select_s"] >= 0.0
    assert sel >= 0.0
