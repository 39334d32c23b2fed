"""Paper Table II: range of per-client relative accuracy change vs the
local-ensemble baseline under the highest heterogeneity Dir(0.1) — the
negative-transfer result (FedPAE's floor is the local ensemble; pFL
baselines can dip below it).

Runs on the declarative spec path: each (dataset, alpha, seed) cell is
one `ExperimentSpec`, the local baseline comes from the same
`Experiment`'s trained models (`local_ensemble()`), so baseline and
FedPAE share data, training, and seeds by construction. When
results/table1.json exists (legacy grid output), its cells are reused
instead of re-training.

Usage:
    PYTHONPATH=src python -m benchmarks.table2_negative_transfer \
        [--full] [--json results/table2.json]

`--json` dumps machine-readable rows ({"name", "min_rel", "max_rel",
"local_frac"}) for CI gates.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.configs.paper_cnn import config as paper_config
from repro.configs.paper_cnn import spec as paper_spec
from repro.sim import Experiment


def run_grid(full=False, alphas=(0.1,), seeds=(0,)):
    """Fresh spec-path grid: {key: {"local": [...], "fedpae": [...],
    "fedpae_local_frac": [...]}} — the same cell shape table1 writes, so
    `negative_transfer` consumes either source."""
    pc = paper_config(full)
    results = {}
    for dname, n_classes in pc["datasets"].items():
        for alpha in alphas:
            for seed in seeds:
                key = f"{dname}|{alpha}|{seed}"
                exp = Experiment.from_spec(paper_spec(
                    pc, n_classes, seed=seed, kind="synthetic_images",
                    n_samples=pc["n_samples"], alpha=alpha))
                local_acc = exp.local_ensemble()
                res = exp.run()
                results[key] = {
                    "local": local_acc.tolist(),
                    "fedpae": res.test_acc.tolist(),
                    "fedpae_local_frac": res.local_frac.tolist(),
                }
                print(f"[{key}] local={local_acc.mean():.3f} "
                      f"fedpae={res.test_acc.mean():.3f}", flush=True)
    return results


def negative_transfer(results):
    """{method: (min_rel, max_rel)} over every Dir(0.1) cell — the
    paper's headline: FedPAE's min_rel stays >= 0 (no negative
    transfer), rounds-based pFL baselines go negative."""
    out = {}
    for key, r in results.items():
        if "|0.1|" not in key:
            continue
        local = np.array(r["local"])
        for m, accs in r.items():
            if m == "local" or m.endswith("_local_frac"):
                continue
            rel = (np.array(accs) - local) / np.maximum(local, 1e-9)
            lo, hi = out.get(m, (np.inf, -np.inf))
            out[m] = (min(lo, float(rel.min())), max(hi, float(rel.max())))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump machine-readable rows for CI gates")
    args = ap.parse_args(argv)
    path = "results/table1.json"
    if os.path.exists(path):
        with open(path) as f:
            results = json.load(f)
    else:
        results = run_grid(full=args.full)
    table = negative_transfer(results)
    fracs = [f for key, r in results.items() if "|0.1|" in key
             for f in r.get("fedpae_local_frac", [])]
    print("method,min_rel_change,max_rel_change")
    rows = []
    for m, (lo, hi) in table.items():
        print(f"{m},{lo:+.1%},{hi:+.1%}")
        rows.append({"name": f"table2_{m}", "min_rel": round(lo, 4),
                     "max_rel": round(hi, 4)})
    if fracs:
        rows.append({"name": "table2_local_frac",
                     "mean": round(float(np.mean(fracs)), 4)})
    if args.json:
        d = os.path.dirname(args.json)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2, allow_nan=False)
        print(f"# wrote {len(rows)} rows to {args.json}")
    return table


if __name__ == "__main__":
    main()
