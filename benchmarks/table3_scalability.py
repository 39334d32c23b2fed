"""Paper Table III: scalability — accuracy at an increased client count
with the total data held constant (less data per client)."""
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


def main(full=False, scale=2, out="results/table3.json"):
    pc = paper_config(full)
    n_clients = pc["n_clients"] * scale  # e.g. 20 -> 50-ish in the paper
    n_classes = list(pc["datasets"].values())[0]
    datasets, _ = make_clients(n_clients, 0.1, pc["n_samples"], n_classes, seed=0)
    fl = FLConfig(rounds=400 if full else 60, local_steps=2,
                  families=pc["train"].families, width=pc["train"].width)
    results = {}
    exp = Experiment(paper_spec(pc, n_classes, n_clients=n_clients),
                     datasets=datasets)
    local_acc = exp.local_ensemble()
    results["local"] = local_acc.tolist()
    res = exp.run()
    results["fedpae"] = res.test_acc.tolist()
    for m in ("fedavg", "feddistill", "lg_fedavg", "fedkd", "fml", "fedgh"):
        results[m] = BASELINES[m](datasets, n_classes, fl).tolist()
    os.makedirs("results", exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1, allow_nan=False)
    print(f"clients={n_clients}")
    print("method,mean_acc,std")
    for m, a in results.items():
        a = np.array(a)
        print(f"{m},{a.mean():.3f},{a.std():.3f}")
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--scale", type=int, default=2)
    a = ap.parse_args()
    main(a.full, a.scale)
