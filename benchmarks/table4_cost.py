"""Paper Table IV: computational-cost comparison.

FLOPs model follows the paper: training FLOPs = 3 x forward FLOPs
(Chiang et al.); FedPAE total = N (M T D f_fwd + P G f_fitness + pf V f_fwd);
round-based methods = N R E f_fwd_bwd. Forward FLOPs per family are
counted analytically from the conv/fc shapes. Runtimes are measured on
the reduced benchmark grid.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import make_clients
from repro.obs.metrics import Stopwatch
from repro.configs.paper_cnn import config as paper_config
from repro.configs.paper_cnn import spec as paper_spec
from repro.fl.baselines import BASELINES, FLConfig
from repro.models.cnn import CNNConfig, init_model
from repro.sim import Experiment


def conv_flops(shape_in, w_shape, stride=1):
    h, w_, cin = shape_in
    kh, kw, _, cout = w_shape
    return 2 * (h // stride) * (w_ // stride) * kh * kw * cin * cout


def family_forward_flops(family: str, ccfg: CNNConfig, img=10):
    """Analytic forward FLOPs for one image."""
    params = init_model(family, jax.random.PRNGKey(0), ccfg)
    total = 0
    for name, leaf in params.items():
        arr = np.asarray(jax.tree.leaves(leaf)[0]) if not hasattr(leaf, "shape") else np.asarray(leaf)
        if arr.ndim == 4:  # conv
            total += conv_flops((img, img, arr.shape[2]), arr.shape)
        elif arr.ndim == 2:  # dense
            total += 2 * arr.shape[0] * arr.shape[1]
    return total


def main(full=False):
    pc = paper_config(full)
    n_classes = list(pc["datasets"].values())[0]
    tr, sel = pc["train"], pc["selection"]
    ccfg = CNNConfig(n_classes=n_classes, width=tr.width)
    datasets, _ = make_clients(pc["n_clients"], 0.1, pc["n_samples"], n_classes)
    N = len(datasets)
    D = int(np.mean([len(d.x_tr) for d in datasets]))
    V = int(np.mean([len(d.x_va) for d in datasets]))

    f_fwd = {f: family_forward_flops(f, ccfg) for f in tr.families}
    f_avg = float(np.mean(list(f_fwd.values())))
    T = tr.max_epochs  # epochs over D samples
    P, G = sel.pop_size, sel.generations
    M = len(tr.families)
    # NSGA fitness evaluation cost: P x (matvec M + quadform M^2) per gen
    f_fit = 2 * (N * M) ** 2 + 2 * N * M
    fedpae_flops = N * (M * 3 * f_avg * T * D + P * G * f_fit + 10 * V * f_avg)

    fl = FLConfig(rounds=400 if full else 60, local_steps=2,
                  families=tr.families, width=tr.width)
    round_flops = N * fl.rounds * fl.local_steps * fl.batch * 3 * f_avg

    rows = [("fedpae_analytic", fedpae_flops), ("round_based_analytic", round_flops)]

    # measured wall-clock on the reduced grid
    exp = Experiment(paper_spec(pc, n_classes), datasets=datasets)
    sw = Stopwatch()
    sw.start()
    exp.local_ensemble()
    t_train = sw.stop()
    sw.start()
    exp.run()
    t_select = sw.stop()
    sw.start()
    BASELINES["fedavg"](datasets, n_classes, fl)
    t_fedavg = sw.stop()

    print("method,gflops_analytic,runtime_s")
    print(f"fedpae,{fedpae_flops/1e9:.2f},{t_train + t_select:.1f}")
    print(f"fedavg,{round_flops/1e9:.2f},{t_fedavg:.1f}")
    print(f"# fedpae breakdown: train {t_train:.1f}s + exchange/select {t_select:.1f}s")
    return {"fedpae_gflops": fedpae_flops / 1e9, "round_gflops": round_flops / 1e9,
            "t_fedpae": t_train + t_select, "t_fedavg": t_fedavg}


if __name__ == "__main__":
    main()
