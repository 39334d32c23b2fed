"""Paper Table I: mean test accuracy across clients, methods x Dir(alpha).

Usage: PYTHONPATH=src python -m benchmarks.table1_accuracy [--full] [--alphas 0.1,0.3,0.5]
Writes results/table1.json; prints the table.
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from benchmarks.common import make_clients
from repro.configs.paper_cnn import config as paper_config
from repro.configs.paper_cnn import spec as paper_spec
from repro.fl.baselines import BASELINES, FLConfig
from repro.sim import Experiment

METHODS = ["fedavg", "fedprox", "feddistill", "lg_fedavg", "fedkd", "fedgh",
           "fml", "local", "fedpae"]


def run_grid(full=False, alphas=None, rounds=None, out="results/table1.json",
             seeds=(0,)):
    pc = paper_config(full)
    alphas = alphas or pc["alphas"]
    results = {}
    for dname, n_classes in pc["datasets"].items():
        for alpha in alphas:
            for seed in seeds:
                key = f"{dname}|{alpha}|{seed}"
                results[key] = {}
                datasets, _ = make_clients(pc["n_clients"], alpha,
                                           pc["n_samples"], n_classes, seed=seed)
                fl = FLConfig(rounds=rounds or (400 if full else 60),
                              local_steps=2,
                              families=pc["train"].families,
                              width=pc["train"].width, seed=seed)
                exp = Experiment(paper_spec(pc, n_classes, seed=seed),
                                 datasets=datasets)
                local_acc = exp.local_ensemble()
                results[key]["local"] = local_acc.tolist()
                res = exp.run()
                results[key]["fedpae"] = res.test_acc.tolist()
                results[key]["fedpae_local_frac"] = res.local_frac.tolist()
                for m in METHODS:
                    if m in ("local", "fedpae"):
                        continue
                    results[key][m] = BASELINES[m](datasets, n_classes, fl).tolist()
                print(f"[{key}] " + " ".join(
                    f"{m}={np.mean(results[key][m]):.3f}"
                    for m in METHODS if m in results[key]), flush=True)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1, allow_nan=False)
    return results


def print_table(results):
    keys = sorted(results)
    print("\nmethod," + ",".join(keys))
    for m in METHODS:
        cells = []
        for k in keys:
            if m in results[k]:
                a = np.array(results[k][m])
                cells.append(f"{a.mean():.3f}±{1.96*a.std()/max(1,len(a))**0.5:.3f}")
            else:
                cells.append("-")
        print(f"{m}," + ",".join(cells))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--alphas", default=None)
    ap.add_argument("--rounds", type=int, default=None)
    a = ap.parse_args()
    alphas = tuple(float(x) for x in a.alphas.split(",")) if a.alphas else None
    print_table(run_grid(a.full, alphas, a.rounds))
