"""Per-client local training of heterogeneous image classifiers.

Step functions are jit-compiled ONCE PER FAMILY (shared across all
clients — same shapes), which is what makes simulating 20-50 clients x 5
model families tractable on one host.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.cnn import CNNConfig, apply_model, init_model
from repro.optim import make_optimizer

EVAL_CHUNK = 256


@dataclasses.dataclass
class ClientData:
    x_tr: np.ndarray
    y_tr: np.ndarray
    x_va: np.ndarray
    y_va: np.ndarray
    x_te: np.ndarray
    y_te: np.ndarray


@lru_cache(maxsize=64)
def _step_fns(family: str, cfg: CNNConfig, opt_name: str, batch: int):
    opt = make_optimizer(opt_name) if opt_name != "momentum" else make_optimizer("momentum")

    def loss_fn(params, xb, yb):
        logits = apply_model(family, params, xb)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], axis=1))

    @jax.jit
    def train_step(params, opt_state, xb, yb, lr):
        loss, grads = jax.value_and_grad(loss_fn)(params, xb, yb)
        params, opt_state = opt.update(grads, opt_state, params, lr)
        return params, opt_state, loss

    @jax.jit
    def predict_chunk(params, xb):
        return jax.nn.softmax(apply_model(family, params, xb), axis=-1)

    return opt, train_step, predict_chunk


def predict_probs(family: str, cfg: CNNConfig, params, x: np.ndarray,
                  opt_name: str = "momentum", batch: int = 32) -> np.ndarray:
    """Chunked, padded inference -> (N, C) probabilities (np.float32)."""
    _, _, predict_chunk = _step_fns(family, cfg, opt_name, batch)
    n = len(x)
    pad = (-n) % EVAL_CHUNK
    xp = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)]) if pad else x
    outs = []
    for i in range(0, len(xp), EVAL_CHUNK):
        outs.append(np.asarray(predict_chunk(params, jnp.asarray(xp[i:i + EVAL_CHUNK]))))
    return np.concatenate(outs)[:n]


@lru_cache(maxsize=64)
def _multi_predict_fn(family: str, cfg: CNNConfig):
    @jax.jit
    def predict_chunk_multi(stacked_params, xb):
        # stacked_params: every leaf gains a leading model axis. A loop
        # over models, not a vmap: vmapped convolutions become grouped
        # convolutions, and the TPU compiler (libtpu 0.0.34) overflows
        # its stack emitting cnn4's at 5 or more stacked models.
        return jax.lax.map(
            lambda p: jax.nn.softmax(apply_model(family, p, xb), axis=-1),
            stacked_params)
    return predict_chunk_multi


def predict_probs_batched(family: str, cfg: CNNConfig, params_seq,
                          x: np.ndarray) -> np.ndarray:
    """Batched multi-model inference: evaluate ALL of one family's models
    on `x` in one jitted call per chunk -> (n_models, N, C).

    This is the exchange-layer hot path: building a client's prediction
    store evaluates n_owners models per family, and stacking their
    parameter trees turns that into a single (n_models, batch) forward
    instead of n_owners separate dispatches.
    """
    params_seq = list(params_seq)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params_seq)
    fn = _multi_predict_fn(family, cfg)
    n = len(x)
    pad = (-n) % EVAL_CHUNK
    xp = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)]) if pad else x
    outs = []
    for i in range(0, len(xp), EVAL_CHUNK):
        outs.append(np.asarray(fn(stacked, jnp.asarray(xp[i:i + EVAL_CHUNK]))))
    return np.concatenate(outs, axis=1)[:, :n]


def accuracy(probs: np.ndarray, y: np.ndarray) -> float:
    return float((probs.argmax(-1) == y).mean())


def train_local_model(family: str, cfg: CNNConfig, seed: int, data: ClientData,
                      *, lr: float = 0.05, batch: int = 32,
                      max_epochs: int = 60, patience: int = 8,
                      opt_name: str = "momentum"):
    """Train one model with early stopping on the client's validation set
    (the paper's protocol: best-val checkpoint is kept).

    Returns (best_params, best_val_acc, history)."""
    opt, train_step, _ = _step_fns(family, cfg, opt_name, batch)
    key = jax.random.PRNGKey(seed)
    params = init_model(family, key, cfg)
    opt_state = opt.init(params)
    rng = np.random.default_rng(seed)
    n = len(data.x_tr)
    steps_per_epoch = max(1, n // batch)

    best_acc, best_params, since_best = -1.0, params, 0
    history = []
    for epoch in range(max_epochs):
        for _ in range(steps_per_epoch):
            idx = rng.integers(0, n, batch)
            params, opt_state, _ = train_step(
                params, opt_state, jnp.asarray(data.x_tr[idx]),
                jnp.asarray(data.y_tr[idx]), jnp.float32(lr))
        va = accuracy(predict_probs(family, cfg, params, data.x_va), data.y_va)
        history.append(va)
        if va > best_acc:
            best_acc, best_params, since_best = va, params, 0
        else:
            since_best += 1
            if since_best >= patience:
                break
    return best_params, best_acc, history
