"""Peer-adaptive ensemble selection (FedPAE §III-A):
NSGA-II over (strength, diversity), then pick the Pareto-front member with
the best OVERALL validation accuracy (mean-prob vote).

`select_ensemble` scores ONE client; `select_ensembles` scores a whole
client batch in one compiled program: per-client acc/S statistics are
vmapped, the genetic loop runs in lockstep via `run_nsga2_batched` with a
distinct PRNG stream per client, and with use_kernel=True the population
of EVERY client is scored by a single batched Pallas launch per
evaluation (DESIGN.md §3).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .nsga2 import NSGAConfig, client_keys, run_nsga2_batched
from .objectives import (ensemble_accuracy, member_accuracy,
                         population_objectives, similarity_matrix)


def _pick_winner(pop, objs, ranks, probs_val, labels_val, acc):
    """Shared post-GA step: best overall-accuracy member of the front."""
    pareto = ranks == 0
    overall = ensemble_accuracy(pop, probs_val, labels_val)
    score = jnp.where(pareto, overall, -1.0)
    best = jnp.argmax(score)
    return {
        "chromosome": pop[best],
        "val_accuracy": overall[best],
        "member_acc": acc,
        "pareto_mask": pareto,
        "pop": pop,
        "objs": objs,
    }


def select_ensemble(probs_val, labels_val, nsga: NSGAConfig,
                    use_kernel: bool = False, key=None, model_mask=None):
    """One client's selection: `select_ensembles` at N=1, so it runs the
    same program as client i of a batch.

    probs_val: (M, V, C) bench predictions on the local validation set.
    `key` — this client's PRNG stream (defaults to PRNGKey(nsga.seed));
    `model_mask` — optional (M,) 0/1 valid-slot mask (padding slots whose
    predictions have not arrived are never selected).

    Returns dict with:
      chromosome (M,) 0/1 — the selected ensemble,
      pop/objs/pareto_mask — the final population and its front (Fig. 3),
      val_accuracy — overall validation accuracy of the winner.
    """
    if key is None:
        key = jax.random.PRNGKey(nsga.seed)
    out = select_ensembles(
        probs_val[None], labels_val[None], nsga, use_kernel=use_kernel,
        keys=key[None],
        model_mask=None if model_mask is None else model_mask[None])
    return {k: v[0] for k, v in out.items()}


@jax.jit
def selection_stats(probs_val, labels_val):
    """The stats stage: (N, M, V, C) + (N, V) -> (acc (N, M), S (N, M, M)).
    Everything the GA consumes; the device-resident store batch
    (core/device_store.py) maintains these incrementally instead of
    recomputing them per select."""
    acc = jax.vmap(member_accuracy)(probs_val, labels_val)          # (N, M)
    S = jax.vmap(similarity_matrix)(probs_val, labels_val)          # (N, M, M)
    return acc, S


def _ga_stage(acc, S, probs_val, labels_val, nsga: NSGAConfig,
              use_kernel: bool, keys, model_mask):
    """The GA stage: NSGA-II over cached (acc, S). `probs_val`/`labels_val`
    are only touched by the winner-picking overall-accuracy vote."""
    N, M = acc.shape
    if keys is None:
        keys = client_keys(nsga.seed, jnp.arange(N))

    if use_kernel:
        from repro.kernels.ensemble_fitness import ops as ef_ops

        def eval_fn(pop):  # (N, P, M) -> (N, P, 2): ONE launch, all clients
            st, dv = ef_ops.ensemble_fitness_batched(pop, acc, S)
            return jnp.stack([st, dv], axis=2)
    else:
        def eval_fn(pop):
            st, dv = jax.vmap(population_objectives)(pop, acc, S)
            return jnp.stack([st, dv], axis=2)

    out = run_nsga2_batched(eval_fn, M, nsga, keys, valid_mask=model_mask)
    return jax.vmap(_pick_winner)(out["pop"], out["objs"], out["ranks"],
                                  probs_val, labels_val, acc)


@partial(jax.jit, static_argnames=("nsga", "use_kernel"))
def select_ensembles(probs_val, labels_val, nsga: NSGAConfig,
                     use_kernel: bool = False, keys=None, model_mask=None):
    """Batched multi-client selection — the vmapped engine.

    probs_val: (N, M, V, C) stacked store tensors (one row per client);
    labels_val: (N, V) with -1 padding; keys: (N, 2) per-client PRNG
    streams (defaults to fold_in(nsga.seed, client_index));
    model_mask: (N, M) 0/1 — which store slots hold arrived predictions.

    Returns the same dict as `select_ensemble` with a leading client axis
    on every value. Stats-stage + GA-stage composed in one jit; callers
    holding cached stats use `select_ensembles_from_stats` instead.
    """
    acc, S = selection_stats(probs_val, labels_val)
    return _ga_stage(acc, S, probs_val, labels_val, nsga, use_kernel,
                     keys, model_mask)


@partial(jax.jit, static_argnames=("nsga", "use_kernel"))
def select_ensembles_from_stats(acc, S, probs_val, labels_val,
                                nsga: NSGAConfig, use_kernel: bool = False,
                                keys=None, model_mask=None):
    """GA stage only: consume CACHED per-client statistics (the
    device-resident incremental path — DESIGN.md §7). `probs_val` is the
    gathered per-client prediction batch the winner-picking vote needs;
    the `O(N·M²·V·C)` stats rebuild is skipped entirely."""
    return _ga_stage(acc, S, probs_val, labels_val, nsga, use_kernel,
                     keys, model_mask)


def local_only_chromosome(is_local, k: int):
    """The all-local fallback ensemble (negative-transfer safety valve):
    up to k LOCAL members and nothing else — with fewer than k local
    models the ensemble is smaller, never padded with remote slots."""
    idx = jnp.argsort(~is_local)  # locals first
    chrom = jnp.zeros(is_local.shape, jnp.float32)
    return chrom.at[idx[:k]].set(1.0) * is_local.astype(jnp.float32)
