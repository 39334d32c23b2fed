"""Selection state split by client over devices (DESIGN.md §15): the
sharded device batch and select against one shard, bit for bit, and
against a float64 reference; the writers fleet through the spec path;
the owner split; and a clear error when devices are missing.

The multi-device checks run once, in a subprocess with four forced CPU
devices (as tests/test_fedpae_pods.py does), and report JSON that the
tests below read: N=10 clients over 4 shards (padded to 12), 16-slot
bounded stores at C=62, V from 5 to 12 per client.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.device_store import split_lanes
from repro.data import split_train_val_test, writers_partition
from repro.data.partition import (FEMNIST_SAMPLES_PER_WRITER,
                                  FEMNIST_WRITERS)
from repro.sim import Experiment
from repro.sim.spec import (DataSpec, ExperimentSpec, ScheduleSpec,
                            SelectionSpec)

CODE = r'''
import json
import numpy as np
from repro.core.bench import BenchEntry, StreamingPredictionStore
from repro.core.engine import SelectionEngine
from repro.core.nsga2 import NSGAConfig
from repro.obs.metrics import Metrics
from repro.sim import Experiment
from repro.sim.spec import (ComponentSpec, DataSpec, ExperimentSpec,
                            NetworkSpec, ScheduleSpec, SelectionSpec)

N, M, C, F = 10, 16, 62, 5
NSGA = NSGAConfig(pop_size=12, generations=4, k=3)
out = {}


def fleet():
    rng = np.random.default_rng(1)
    return [StreamingPredictionStore(
        c, M, np.zeros((v, 1), np.float32), rng.integers(0, C, v), C)
        for c, v in enumerate(rng.integers(5, 13, N))]


def entry(gid):
    return BenchEntry(model_id=gid, owner=gid // F, family="f",
                      predict=None)


def state(dev):
    return [np.asarray(getattr(dev, k))[:N]
            for k in ("preds", "pnorm", "masks", "acc", "S")]


def reference(store):
    """float64 hits and S of a store's rows (benchmarks/chip/reference)."""
    rows, lab = store.preds.astype(np.float64), store.labels
    valid = lab >= 0
    nv = max(int(valid.sum()), 1)
    hits = ((rows.argmax(-1) == lab[None]) & valid[None]).sum(-1)
    g = rows / (np.linalg.norm(rows, axis=-1, keepdims=True) + 1e-12)
    g = (g * valid[None, :, None]).reshape(M, -1)
    return hits, g @ g.T / nv, nv


def fitness(pop, hits, S, nv):
    c = (pop > 0.5).astype(np.float64)
    k = c.sum(1)
    strength = (c @ hits) / np.maximum(k, 1.0) / nv
    off = S - np.diag(np.diag(S))
    pairs = np.einsum("pm,mn,pn->p", c, off, c)
    return np.stack([strength, 1.0 - pairs / np.maximum(k * (k - 1.0), 1.0)], 1)


# ---- rewrites and evictions through both engines --------------------
fleets = [fleet(), fleet()]
mx = Metrics()
engines = [SelectionEngine(fleets[0], NSGA, seed=5),
           SelectionEngine(fleets[1], NSGA, seed=5, shards=4, metrics=mx)]
rng = np.random.default_rng(2)
same_chrom, objs_gap = True, 0.0
for step in range(10):
    clients = sorted(rng.choice(N, int(rng.integers(1, N + 1)),
                                replace=False).tolist())
    for c in clients:
        for _ in range(int(rng.integers(2, 7))):
            gid = int(rng.integers(N * F))
            row = rng.random((fleets[0][c].n_val, C)).astype(np.float32)
            for f in fleets:
                f[c].add(entry(gid), preds=row, t=float(step))
    one, four = (e.select(clients, t=float(step)) for e in engines)
    assert sorted(one) == sorted(four)
    for c in one:
        same_chrom &= bool((one[c]["chromosome"]
                            == four[c]["chromosome"]).all())
        objs_gap = max(objs_gap, float(np.abs(one[c]["objs"]
                                              - four[c]["objs"]).max()))
dev1, dev4 = engines[0].device, engines[1].device
out["bit_equal"] = {k: bool(np.array_equal(a, b)) for k, a, b in zip(
    ("preds", "pnorm", "masks", "acc", "S"), state(dev1), state(dev4))}
out["same_chromosomes"] = same_chrom
out["objs_gap_1_vs_4"] = objs_gap
out["evictions"] = [sum(s.evictions for s in f) for f in fleets]
out["rows_evicted"] = [dev1.n_rows_evicted, dev4.n_rows_evicted]
out["padded_clients"] = int(dev4.preds.shape[0])
out["shard_of_preds"] = [int(s.data.shape[0])
                         for s in dev4.preds.addressable_shards]
gaps = {"acc_count": 0, "S": 0.0, "objs": 0.0}
for name, eng in zip(("one", "four"), engines):
    acc, S = np.asarray(eng.device.acc), np.asarray(eng.device.S)
    for c, store in enumerate(eng.stores):
        hits, R, nv = reference(store)
        gaps["acc_count"] = max(gaps["acc_count"], int(np.abs(
            np.rint(acc[c].astype(np.float64) * nv) - hits).max()))
        gaps["S"] = max(gaps["S"], float(np.abs(S[c] - R).max()))
        res = eng.results.get(c)
        if res is not None:
            gaps["objs"] = max(gaps["objs"], float(np.abs(
                res["objs"] - fitness(res["pop"], hits, R, nv)).max()))
out["reference_gaps"] = gaps
names = mx.frame().names()
out["obs"] = {
    "spans": sorted({r.name for r in mx.spans()}),
    "evicted_rows": mx.frame().scalars.get("store.evicted_rows"),
    "lanes": mx.frame().scalars.get("engine.select.lanes"),
    "pad_lanes": mx.frame().scalars.get("engine.select.pad_lanes"),
    "n_lanes": engines[1].n_lanes, "n_pad_lanes": engines[1].n_pad_lanes,
    "has_shard_rows": any(n.startswith("store.shard_rows") for n in names),
    "has_shard_width": "engine.select.shard_width" in names}
try:
    engines[1].add_store(fleet()[0])
    out["append_error"] = None
except NotImplementedError as e:
    out["append_error"] = str(e)


# ---- the writers fleet through Experiment.from_spec ------------------
def spec(shards):
    return ExperimentSpec(
        data=DataSpec(kind="prediction_world", partition="writers",
                      n_clients=N, n_classes=C, models_per_client=F,
                      seed=3),
        selection=SelectionSpec(pop_size=8, generations=3, k=3,
                                store_capacity=12, shards=shards),
        network=NetworkSpec(topology="random", topology_k=4,
                            transport=ComponentSpec("gossip"),
                            gossip="push"),
        schedule=ScheduleSpec(mode="async", select_debounce=0.5,
                              train_cost=ComponentSpec(
                                  "affine", {"base": 1.0, "slope": 0.2})),
        seed=0)


runs = [Experiment.from_spec(spec(s)).run() for s in (1, 4)]
e1, e4 = runs[0].engine, runs[1].engine
out["exp"] = {
    "same_trace": runs[0].trace.events == runs[1].trace.events,
    "bit_equal": all(np.array_equal(a, b) for a, b in zip(
        state(e1.device), state(e4.device))),
    "same_answers": all(
        (e1.results[c]["chromosome"] == e4.results[c]["chromosome"]).all()
        for c in e1.results) and sorted(e1.results) == sorted(e4.results),
    "selects": e4.n_lanes - e4.n_pad_lanes,
    "evictions": sum(s.evictions for s in runs[1].stores),
    "n_val": [s.n_val for s in runs[1].stores]}
print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run([sys.executable, "-c", CODE], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sharded_stats_are_bit_equal_to_one_shard(four):
    assert four["bit_equal"] == {k: True for k in
                                 ("preds", "pnorm", "masks", "acc", "S")}
    assert four["evictions"][0] == four["evictions"][1] > 0
    assert four["rows_evicted"] == four["evictions"]
    # 10 clients over 4 shards: 3 a shard, client axis padded to 12
    assert four["padded_clients"] == 12
    assert four["shard_of_preds"] == [3, 3, 3, 3]


def test_sharded_answers_equal_one_shard(four):
    assert four["same_chromosomes"]
    assert four["objs_gap_1_vs_4"] <= 1e-6


def test_both_agree_with_the_float64_reference(four):
    gaps = four["reference_gaps"]
    # paper_c100's limits (benchmarks/chip/configs/paper_c100.json)
    assert gaps["acc_count"] == 0
    assert gaps["S"] <= 7e-5
    assert gaps["objs"] <= 2.5e-5


def test_sharded_spans_and_counters(four):
    obs = four["obs"]
    assert {"store.flush.route", "engine.select.split"} <= set(obs["spans"])
    assert obs["evicted_rows"] == four["rows_evicted"][1]
    assert obs["lanes"] == obs["n_lanes"] > 0
    assert obs["pad_lanes"] == obs["n_pad_lanes"] > 0
    assert obs["has_shard_rows"] and obs["has_shard_width"]


def test_sharded_batch_refuses_a_late_joiner(four):
    assert four["append_error"] and "fixed" in four["append_error"]


def test_writers_fleet_from_spec_on_four_shards_matches_one(four):
    exp = four["exp"]
    assert exp["same_trace"] and exp["bit_equal"] and exp["same_answers"]
    assert exp["selects"] > 0 and exp["evictions"] > 0
    assert len(set(exp["n_val"])) > 1      # per-writer validation widths


def test_more_shards_than_devices_is_a_clear_error():
    spec = ExperimentSpec(
        data=DataSpec(kind="prediction_world", partition="writers",
                      n_clients=4, n_classes=10, models_per_client=2),
        selection=SelectionSpec(pop_size=8, generations=2, k=2,
                                shards=64),
        schedule=ScheduleSpec(mode="async"))
    with pytest.raises(ValueError, match="shards=64 needs 64 devices"):
        Experiment.from_spec(spec).build()


def test_zero_shards_is_an_error():
    from repro.core.bench import PredictionStore
    from repro.core.device_store import DeviceStoreBatch
    store = PredictionStore(0, 4, np.zeros((3, 1)), np.arange(3), 5)
    with pytest.raises(ValueError, match="shards=0"):
        DeviceStoreBatch([store], shards=0)


def test_owner_split_pads_each_shard_with_its_own_client():
    lanes, pos = split_lanes([9, 1, 2, 0, 5], n_local=4, shards=3)
    # shard 0 holds 1, 2, 0 (W = pow2(3) = 4); shard 1 holds 5; shard 2 holds 9
    assert lanes == [1, 2, 0, 1, 5, 5, 5, 5, 9, 9, 9, 9]
    assert [lanes[p] for p in pos] == [9, 1, 2, 0, 5]
    lanes, _ = split_lanes([4], n_local=4, shards=3)
    assert lanes == [0, 4, 8]          # shards with none: their first client
    # one shard: the batch padded to a power of two by its first client
    assert split_lanes([3, 1, 2], n_local=5, shards=1) == ([3, 1, 2, 3],
                                                          [0, 1, 2])


def test_writers_partition_draws_leaf_sizes():
    labels, parts = writers_partition(FEMNIST_WRITERS, 62,
                                      *FEMNIST_SAMPLES_PER_WRITER, seed=0)
    sizes = np.array([len(p) for p in parts])
    assert abs(sizes.mean() - 226.83) < 5 and abs(sizes.std() - 88.94) < 5
    assert sizes.min() >= 12 and len(labels) == sizes.sum()
    assert labels.min() == 0 and labels.max() == 61
    val = [len(split_train_val_test(p, seed=1)[1]) for p in parts]
    assert max(val) <= 80 and 30 < np.mean(val) < 37


@pytest.mark.parametrize("kw,match", [
    ({"kind": "prediction_world", "partition": "leaf"}, "partition"),
    ({"kind": "synthetic_images", "partition": "writers"}, "writers")])
def test_data_spec_rejects_a_bad_partition(kw, match):
    with pytest.raises(ValueError, match=match):
        DataSpec(**kw)
