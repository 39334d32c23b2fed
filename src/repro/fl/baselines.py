"""The paper's eight comparison methods on the shared substrate.

Synchronous, server-based rounds (that is the point of comparison: FedPAE
is the only fully decentralized/asynchronous method in the table).

  fedavg     — McMahan et al. 2017, homogeneous cnn4
  fedprox    — + proximal term mu/2 ||w - w_global||^2
  feddistill — share per-class mean logits, distill to local models (het.)
  lg_fedavg  — average the homogeneous classifier head only (het. bodies)
  fedgh      — server trains a generalized global header on uploaded
               per-class feature prototypes (het. bodies)
  fml        — mutual distillation with a shared small aux model (cnn4)
  fedkd      — like FML with scheduled distillation weight + aux averaging
  local      — per-client local ensemble (`Experiment.local_ensemble`)
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.client import ClientData, accuracy, predict_probs
from repro.models.cnn import (CNNConfig, FEAT_MULT, apply_features,
                              apply_model, init_model)

DEFAULT_FAMILIES = ("cnn4", "vgg", "resnet", "densenet", "inception")


@dataclasses.dataclass
class FLConfig:
    rounds: int = 150
    local_steps: int = 4
    lr: float = 0.05
    batch: int = 32
    mu: float = 0.01          # fedprox
    beta: float = 1.0         # distillation weight
    families: tuple = DEFAULT_FAMILIES
    width: int = 16
    seed: int = 0


def _ce(logits, y):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _kl(p_logits, q_logits, T=1.0):
    """KL(softmax(p) || softmax(q)) mean over batch."""
    p = jax.nn.log_softmax(p_logits / T)
    q = jax.nn.log_softmax(q_logits / T)
    return jnp.mean(jnp.sum(jnp.exp(p) * (p - q), axis=-1))


def _avg(trees, weights):
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    return jax.tree.map(lambda *ls: sum(wi * l for wi, l in zip(w, ls)), *trees)


def _sample(rng, data: ClientData, batch):
    idx = rng.integers(0, len(data.x_tr), batch)
    return jnp.asarray(data.x_tr[idx]), jnp.asarray(data.y_tr[idx])


# --------------------------------------------------------------------------
# FedAvg / FedProx
# --------------------------------------------------------------------------

@lru_cache(maxsize=8)
def _fedavg_step(family: str, cfg: CNNConfig, mu: float):
    def loss(p, pg, xb, yb):
        l = _ce(apply_model(family, p, xb), yb)
        if mu:
            sq = sum(jnp.sum((a - b) ** 2) for a, b in
                     zip(jax.tree.leaves(p), jax.tree.leaves(pg)))
            l = l + 0.5 * mu * sq
        return l

    @jax.jit
    def step(p, pg, xb, yb, lr):
        g = jax.grad(loss)(p, pg, xb, yb)
        return jax.tree.map(lambda a, b: a - lr * b, p, g)
    return step


def run_fedavg(datasets, n_classes, fl: FLConfig, prox: bool = False):
    ccfg = CNNConfig(n_classes=n_classes, width=fl.width,
                     in_channels=datasets[0].x_tr.shape[-1])
    fam = "cnn4"
    step = _fedavg_step(fam, ccfg, fl.mu if prox else 0.0)
    rng = np.random.default_rng(fl.seed)
    g = init_model(fam, jax.random.PRNGKey(fl.seed), ccfg)
    sizes = [len(d.x_tr) for d in datasets]
    for _ in range(fl.rounds):
        locals_ = []
        for data in datasets:
            p = g
            for _ in range(fl.local_steps):
                xb, yb = _sample(rng, data, fl.batch)
                p = step(p, g, xb, yb, jnp.float32(fl.lr))
            locals_.append(p)
        g = _avg(locals_, sizes)
    return np.array([accuracy(predict_probs(fam, ccfg, g, d.x_te), d.y_te)
                     for d in datasets])


# --------------------------------------------------------------------------
# FedDistill: share per-class mean logits
# --------------------------------------------------------------------------

@lru_cache(maxsize=16)
def _distill_step(family: str, cfg: CNNConfig, beta: float):
    def loss(p, xb, yb, glob_logits, have_glob):
        logits = apply_model(family, p, xb)
        l = _ce(logits, yb)
        tgt = glob_logits[yb]  # (B, C) global mean logits of the true class
        l = l + beta * have_glob * jnp.mean((logits - tgt) ** 2)
        return l

    @jax.jit
    def step(p, xb, yb, glob_logits, have_glob, lr):
        g = jax.grad(loss)(p, xb, yb, glob_logits, have_glob)
        return jax.tree.map(lambda a, b: a - lr * b, p, g)

    @jax.jit
    def class_logits(p, x, y, n_cls):
        logits = apply_model(family, p, x)
        onehot = jax.nn.one_hot(y, n_cls.shape[0], dtype=jnp.float32)
        sums = onehot.T @ logits
        cnts = jnp.maximum(onehot.sum(0)[:, None], 1.0)
        return sums / cnts, onehot.sum(0)
    return step, class_logits


def run_feddistill(datasets, n_classes, fl: FLConfig):
    ccfg = CNNConfig(n_classes=n_classes, width=fl.width,
                     in_channels=datasets[0].x_tr.shape[-1])
    fams = [fl.families[i % len(fl.families)] for i in range(len(datasets))]
    rng = np.random.default_rng(fl.seed)
    params = [init_model(f, jax.random.PRNGKey(fl.seed + i), ccfg)
              for i, f in enumerate(fams)]
    glob = np.zeros((n_classes, n_classes), np.float32)
    have = 0.0
    ncls_probe = jnp.zeros((n_classes,))
    for r in range(fl.rounds):
        sums = np.zeros_like(glob)
        cnts = np.zeros((n_classes,), np.float32)
        for i, data in enumerate(datasets):
            step, class_logits = _distill_step(fams[i], ccfg, fl.beta)
            for _ in range(fl.local_steps):
                xb, yb = _sample(rng, data, fl.batch)
                params[i] = step(params[i], xb, yb, jnp.asarray(glob),
                                 jnp.float32(have), jnp.float32(fl.lr))
            cl, cc = class_logits(params[i], jnp.asarray(data.x_tr[:256]),
                                  jnp.asarray(data.y_tr[:256]), ncls_probe)
            sums += np.asarray(cl) * np.asarray(cc)[:, None]
            cnts += np.asarray(cc)
        glob = sums / np.maximum(cnts, 1.0)[:, None]
        have = 1.0
    return np.array([accuracy(predict_probs(fams[i], ccfg, params[i], d.x_te), d.y_te)
                     for i, d in enumerate(datasets)])


# --------------------------------------------------------------------------
# LG-FedAvg: average only the homogeneous head
# --------------------------------------------------------------------------

def run_lg_fedavg(datasets, n_classes, fl: FLConfig):
    ccfg = CNNConfig(n_classes=n_classes, width=fl.width,
                     in_channels=datasets[0].x_tr.shape[-1])
    fams = [fl.families[i % len(fl.families)] for i in range(len(datasets))]
    rng = np.random.default_rng(fl.seed)
    params = [init_model(f, jax.random.PRNGKey(fl.seed + i), ccfg)
              for i, f in enumerate(fams)]
    sizes = [len(d.x_tr) for d in datasets]
    for r in range(fl.rounds):
        for i, data in enumerate(datasets):
            step = _fedavg_step(fams[i], ccfg, 0.0)
            for _ in range(fl.local_steps):
                xb, yb = _sample(rng, data, fl.batch)
                params[i] = step(params[i], params[i], xb, yb, jnp.float32(fl.lr))
        head = _avg([{"head": p["head"]} for p in params], sizes)["head"]
        for p in params:
            p["head"] = head
    return np.array([accuracy(predict_probs(fams[i], ccfg, params[i], d.x_te), d.y_te)
                     for i, d in enumerate(datasets)])


# --------------------------------------------------------------------------
# FedGH: server-side generalized global header on feature prototypes
# --------------------------------------------------------------------------

def run_fedgh(datasets, n_classes, fl: FLConfig):
    ccfg = CNNConfig(n_classes=n_classes, width=fl.width,
                     in_channels=datasets[0].x_tr.shape[-1])
    fams = [fl.families[i % len(fl.families)] for i in range(len(datasets))]
    rng = np.random.default_rng(fl.seed)
    params = [init_model(f, jax.random.PRNGKey(fl.seed + i), ccfg)
              for i, f in enumerate(fams)]
    feat_dim = FEAT_MULT * fl.width

    @jax.jit
    def head_step(head, protos, labels, lr):
        def loss(h):
            return _ce(protos @ h, labels)
        return head - lr * jax.grad(loss)(head)

    protos_fn = {}
    for f in sorted(set(fams)):
        @jax.jit
        def pf(p, x, y, f=f):
            feats = apply_features(f, p, x)
            onehot = jax.nn.one_hot(y, n_classes, dtype=jnp.float32)
            sums = onehot.T @ feats
            cnts = jnp.maximum(onehot.sum(0)[:, None], 1.0)
            return sums / cnts, onehot.sum(0)
        protos_fn[f] = pf

    head = np.asarray(init_model("cnn4", jax.random.PRNGKey(0), ccfg)["head"])
    for r in range(fl.rounds):
        all_protos, all_labels = [], []
        for i, data in enumerate(datasets):
            step = _fedavg_step(fams[i], ccfg, 0.0)
            params[i]["head"] = jnp.asarray(head)
            for _ in range(fl.local_steps):
                xb, yb = _sample(rng, data, fl.batch)
                params[i] = step(params[i], params[i], xb, yb, jnp.float32(fl.lr))
            pr, cc = protos_fn[fams[i]](params[i], jnp.asarray(data.x_tr[:256]),
                                        jnp.asarray(data.y_tr[:256]))
            present = np.asarray(cc) > 0
            all_protos.append(np.asarray(pr)[present])
            all_labels.append(np.where(present)[0])
        protos = jnp.asarray(np.concatenate(all_protos))
        labels = jnp.asarray(np.concatenate(all_labels).astype(np.int32))
        h = jnp.asarray(head)
        for _ in range(5):
            h = head_step(h, protos, labels, jnp.float32(fl.lr))
        head = np.asarray(h)
    for i in range(len(params)):
        params[i]["head"] = jnp.asarray(head)
    return np.array([accuracy(predict_probs(fams[i], ccfg, params[i], d.x_te), d.y_te)
                     for i, d in enumerate(datasets)])


# --------------------------------------------------------------------------
# FML / FedKD: mutual distillation with a shared small auxiliary model
# --------------------------------------------------------------------------

@lru_cache(maxsize=16)
def _mutual_step(family: str, cfg: CNNConfig):
    def losses(p_big, p_aux, xb, yb, beta):
        lb = apply_model(family, p_big, xb)
        la = apply_model("cnn4", p_aux, xb)
        l_big = _ce(lb, yb) + beta * _kl(jax.lax.stop_gradient(la), lb)
        l_aux = _ce(la, yb) + beta * _kl(jax.lax.stop_gradient(lb), la)
        return l_big + l_aux

    @jax.jit
    def step(p_big, p_aux, xb, yb, beta, lr):
        gb, ga = jax.grad(losses, argnums=(0, 1))(p_big, p_aux, xb, yb, beta)
        nb = jax.tree.map(lambda a, b: a - lr * b, p_big, gb)
        na = jax.tree.map(lambda a, b: a - lr * b, p_aux, ga)
        return nb, na
    return step


def run_fml(datasets, n_classes, fl: FLConfig, schedule_beta: bool = False):
    """FML (schedule_beta=False) / FedKD (True: distill weight ramps up)."""
    ccfg = CNNConfig(n_classes=n_classes, width=fl.width,
                     in_channels=datasets[0].x_tr.shape[-1])
    fams = [fl.families[i % len(fl.families)] for i in range(len(datasets))]
    rng = np.random.default_rng(fl.seed)
    params = [init_model(f, jax.random.PRNGKey(fl.seed + i), ccfg)
              for i, f in enumerate(fams)]
    aux_g = init_model("cnn4", jax.random.PRNGKey(fl.seed - 1), ccfg)
    sizes = [len(d.x_tr) for d in datasets]
    for r in range(fl.rounds):
        beta = fl.beta * ((r + 1) / fl.rounds if schedule_beta else 1.0)
        aux_locals = []
        for i, data in enumerate(datasets):
            step = _mutual_step(fams[i], ccfg)
            aux = aux_g
            for _ in range(fl.local_steps):
                xb, yb = _sample(rng, data, fl.batch)
                params[i], aux = step(params[i], aux, xb, yb,
                                      jnp.float32(beta), jnp.float32(fl.lr))
            aux_locals.append(aux)
        aux_g = _avg(aux_locals, sizes)
    return np.array([accuracy(predict_probs(fams[i], ccfg, params[i], d.x_te), d.y_te)
                     for i, d in enumerate(datasets)])


def run_fedkd(datasets, n_classes, fl: FLConfig):
    return run_fml(datasets, n_classes, fl, schedule_beta=True)


BASELINES = {
    "fedavg": lambda d, n, fl: run_fedavg(d, n, fl, prox=False),
    "fedprox": lambda d, n, fl: run_fedavg(d, n, fl, prox=True),
    "feddistill": run_feddistill,
    "lg_fedavg": run_lg_fedavg,
    "fedgh": run_fedgh,
    "fml": run_fml,
    "fedkd": run_fedkd,
}
