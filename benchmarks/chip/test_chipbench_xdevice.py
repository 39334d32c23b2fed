"""The cross-device cell on four forced CPU devices: femnist_x4_async,
tiny, through `run.run_cell` and `control.readings` in a subprocess (the
devices are fixed when JAX starts), and the writers' labels against the
program's, draw for draw."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chip import harness
from chip.conftest import TINY
from chip.data.writers import writer_val_labels

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "femnist_x4_async"
SEED = 2**31 + 977
# tiny, with stores smaller than the fleet's 40 models, so that they fill
# and every new model evicts
OVER = dict(TINY, n_clients=8, store_slots=8)

CODE = r'''
import json, sys
sys.path[:0] = sys.argv[1:3]
from chip import harness
from chip.control import readings
from chip.run import run_cell
cell, seed, over = sys.argv[3], int(sys.argv[4]), json.loads(sys.argv[5])
lines, seen = [], {}


def before(wl):
    seen["stores"] = wl.stores
    seen["evicted"] = sum(s.evictions for s in wl.stores)


out = run_cell(cell, seed, 0.3, True, t_start=harness.now(), overrides=over,
               log=lines.append, before_window=before)
out["window_line"] = [ln for ln in lines if ln.startswith("window:")][0]
out["describe"] = lines[0]
out["evicted_in_window"] = (sum(s.evictions for s in seen["stores"])
                            - seen["evicted"])
out["control"] = readings(cell, seed, 0.3, t_start=harness.now(),
                          overrides=over)
print("RESULT " + json.dumps(out, default=float))
'''


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    root = os.path.dirname(os.path.dirname(HERE))
    proc = subprocess.run(
        [sys.executable, "-c", CODE, os.path.join(root, "src"),
         os.path.dirname(HERE), CELL, str(SEED),
         json.dumps(OVER, allow_nan=False)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_cell_on_four_shards_is_correct_evicts_and_reports_padding(four):
    assert four["correct"], four["checks"]
    assert four["window_line"].endswith(" 0 XLA compiles inside it")
    assert '"shards": 4' in four["describe"]
    assert four["evicted_in_window"] >= 1
    assert "shard_pad_pct" in four["metrics"]
    assert four["checks"]["answers_checked"]["value"] >= 8


def test_control_at_one_pass_exceeds_a_limit_and_the_program_does_not(four):
    got = four["control"]
    assert got["correct"]
    limits = harness.load_config(harness.find_cell(
        harness.manifest(), CELL)[1])["limits"]["reselect_xdevice"]
    assert any(got["control_1pass"][k] > lim for k, lim in limits.items())
    assert all(got["program"][k] <= lim for k, lim in limits.items())


def test_writers_labels_match_the_program_draw_for_draw():
    from repro.sim.build import build_prediction_world
    from repro.sim.spec import DataSpec
    cfg = harness.load_config(harness.find_cell(harness.manifest(),
                                                CELL)[1])
    cfg = dict(cfg, n_clients=60)
    data = DataSpec(kind="prediction_world", partition="writers",
                    n_clients=60, n_classes=cfg["n_classes"],
                    models_per_client=1,
                    samples_per_writer=tuple(cfg["samples_per_writer"]),
                    seed=cfg["data_seed"])
    labels, _ = build_prediction_world(data, default_seed=99)
    ours = writer_val_labels(cfg)
    assert len(ours) == 60
    for c in range(60):
        np.testing.assert_array_equal(ours[c], labels[c])


def _tiny_workload():
    from chip.drivers import reselect_xdevice
    cell, cfg_entry = harness.find_cell(harness.manifest(), CELL)
    cfg = dict(harness.load_config(cfg_entry), **OVER)
    mix = harness.load_traffic(cell["traffic"])
    return reselect_xdevice.Workload(cfg, mix, harness.Seeds(SEED)), cfg


def test_reference_rows_are_made_again_from_the_seed():
    """The reference's rows come from the driver's own record and the
    seed, and equal what the program's stores hold when they follow the
    eviction policy."""
    wl, cfg = _tiny_workload()
    evicted = sum(s.evictions for s in wl.stores)
    for _ in range(6):
        wl.step(split=False)
    assert sum(s.evictions for s in wl.stores) > evicted
    snap = wl.snapshot()
    assert wl.slot_miss == 0
    for j, c in enumerate(snap["clients"]):
        rows = wl.rows(j, snap["variant"][j])
        assert rows.shape == (cfg["store_slots"], wl.n_val[c],
                              cfg["n_classes"])
        np.testing.assert_array_equal(rows,
                                      wl.stores[c].preds[:, :wl.n_val[c]])
    checks = {c["name"]: c for c in
              wl.check(snap, cfg["limits"]["reselect_xdevice"])}
    assert all(c["value"] <= c["limit"] for c in checks.values()
               if c["limit"] is not None), checks


def test_a_store_that_evicts_the_wrong_slot_is_caught(monkeypatch):
    """A store that evicts its most-selected unpinned slot, not its
    least-selected: the driver's record disagrees on the slot, and the
    reference, made from that record, no longer matches the program."""
    from repro.core.bench import StreamingPredictionStore

    def evict_last(self):
        cand = np.flatnonzero(self._evictable())
        order = np.lexsort((cand, self.last_used[cand], self.hits[cand]))
        slot = int(cand[order[-1]])
        self._clear_slot(slot)
        self.evictions += 1
        return slot
    monkeypatch.setattr(StreamingPredictionStore, "_evict_one", evict_last)
    wl, cfg = _tiny_workload()
    for _ in range(6):
        wl.step(split=False)
    snap = wl.snapshot()
    checks = {c["name"]: c["value"] for c in
              wl.check(snap, cfg["limits"]["reselect_xdevice"])}
    assert checks["slot_miss"] >= 1
    assert checks["S_gap"] > cfg["limits"]["reselect_xdevice"]["S_gap"]
