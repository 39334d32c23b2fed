"""NSGA-II (Deb et al. 2002), fully vectorized in JAX.

The whole genetic loop is a single `lax.scan` over generations; every
generation evaluates the entire population with two matmuls (see
objectives.py), computes dominance (P x P boolean algebra), peels fronts
with a `while_loop`, and applies tournament selection / uniform crossover
/ bit-flip mutation / exact-k repair as vectorized bit ops. On TPU this
turns the paper's per-client CPU hot loop into an MXU-shaped batch job.

Two entry points, one program (DESIGN.md §3):

  run_nsga2_batched  — N clients at once: the per-generation genetic ops
                       are `jax.vmap`-ed over the client axis while the
                       objective evaluation sees the whole (N, P, M)
                       population in one call (so a batched Pallas kernel
                       can score every client's population in one launch).
  run_nsga2          — one client's GA: the batched run at N=1, explicit
                       `key` (falls back to `cfg.seed` for backwards
                       compatibility).

Each client gets its OWN PRNG stream (`keys[(N, 2)]`); clients no longer
share one GA random sequence through `NSGAConfig.seed`.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

BIG = jnp.float32(1e9)


class NSGAConfig(NamedTuple):
    pop_size: int = 100
    generations: int = 100
    k: int = 5            # exact ensemble size (0 = free size)
    p_mut: float = 0.02
    p_cross: float = 0.9
    seed: int = 0


def dominance(objs):
    """objs: (P, n_obj), maximized. dom[i, j] = i dominates j."""
    ge = jnp.all(objs[:, None, :] >= objs[None, :, :], axis=-1)
    gt = jnp.any(objs[:, None, :] > objs[None, :, :], axis=-1)
    return ge & gt


def nondominated_rank(objs):
    """(P,) rank per individual (0 = Pareto front) by iterative peeling."""
    P = objs.shape[0]
    dom = dominance(objs)  # (P, P)

    def cond(state):
        ranks, remaining, r = state
        return jnp.any(remaining) & (r < P)

    def body(state):
        ranks, remaining, r = state
        dominated = jnp.any(dom & remaining[:, None] & remaining[None, :], axis=0)
        front = remaining & ~dominated
        ranks = jnp.where(front, r, ranks)
        return ranks, remaining & ~front, r + 1

    ranks0 = jnp.full((P,), P, jnp.int32)
    ranks, _, _ = jax.lax.while_loop(
        cond, body, (ranks0, jnp.ones((P,), bool), jnp.int32(0)))
    return ranks


def crowding_distance(objs, ranks):
    """(P,) crowding distance computed within each rank front."""
    P, n_obj = objs.shape
    dist = jnp.zeros((P,), jnp.float32)
    for m in range(n_obj):
        v = objs[:, m]
        key = ranks.astype(jnp.float32) * BIG + v
        order = jnp.argsort(key)  # sorted by (rank, value)
        v_sorted = v[order]
        r_sorted = ranks[order]
        prev_ok = jnp.concatenate([jnp.array([False]), r_sorted[1:] == r_sorted[:-1]])
        next_ok = jnp.concatenate([r_sorted[1:] == r_sorted[:-1], jnp.array([False])])
        prev_v = jnp.concatenate([v_sorted[:1], v_sorted[:-1]])
        next_v = jnp.concatenate([v_sorted[1:], v_sorted[-1:]])
        span = jnp.maximum(jnp.max(v) - jnp.min(v), 1e-12)
        contrib = jnp.where(prev_ok & next_ok, (next_v - prev_v) / span, BIG)
        dist = dist.at[order].add(contrib)
    return dist


def _tournament(key, ranks, crowd, n):
    """Binary tournament: lower rank wins, ties by higher crowding."""
    P = ranks.shape[0]
    idx = jax.random.randint(key, (2, n), 0, P)
    a, b = idx[0], idx[1]
    a_better = (ranks[a] < ranks[b]) | ((ranks[a] == ranks[b]) & (crowd[a] > crowd[b]))
    return jnp.where(a_better, a, b)


def repair_k(pop_f, key, k: int, valid_mask=None):
    """Force exactly k ones per row: keep set bits with priority, fill the
    rest randomly. pop_f: (P, M) float 0/1. With `valid_mask` (M,) 0/1,
    masked-out slots score below every valid slot and can never be set —
    rows end up with min(k, #valid) ones."""
    P, M = pop_f.shape
    noise = jax.random.uniform(key, (P, M))
    score = pop_f * 2.0 + noise  # existing bits rank above absent ones
    if valid_mask is not None:
        score = score - (1.0 - valid_mask) * 8.0
    thresh = -jnp.sort(-score, axis=1)[:, k - 1:k]  # k-th largest
    rep = (score >= thresh).astype(jnp.float32)
    if valid_mask is not None:
        rep = rep * valid_mask
    return rep


def _init_population(k0, k1, P, M, k, valid_mask=None, init_pop=None):
    if init_pop is None:
        pop = (jax.random.uniform(k0, (P, M)) < 0.5).astype(jnp.float32)
    else:
        pop = init_pop.astype(jnp.float32)
    if valid_mask is not None:
        pop = pop * valid_mask
    if k:
        pop = repair_k(pop, k1, k, valid_mask)
    return pop


def _breed(pop, ranks, crowd, key_g, cfg: NSGAConfig, valid_mask=None):
    """One client's offspring: tournament -> uniform crossover -> bit-flip
    mutation -> exact-k repair. Six independent key draws (the crossover
    mask and the per-row crossover gate use SEPARATE keys)."""
    P, M = pop.shape
    ks = jax.random.split(key_g, 6)
    parents_a = pop[_tournament(ks[0], ranks, crowd, P)]
    parents_b = pop[_tournament(ks[1], ranks, crowd, P)]
    cross = (jax.random.uniform(ks[2], (P, M)) < 0.5).astype(jnp.float32)
    do_cross = (jax.random.uniform(ks[3], (P, 1)) < cfg.p_cross).astype(jnp.float32)
    child = parents_a * (1 - cross * do_cross) + parents_b * cross * do_cross
    flip = (jax.random.uniform(ks[4], (P, M)) < cfg.p_mut).astype(jnp.float32)
    child = jnp.abs(child - flip)
    if valid_mask is not None:
        child = child * valid_mask
    if cfg.k:
        child = repair_k(child, ks[5], cfg.k, valid_mask)
    return child


def _survival_order(aobjs):
    """(2P, n_obj) -> survival sort order (rank asc, crowding desc)."""
    aranks = nondominated_rank(aobjs)
    acrowd = crowding_distance(aobjs, aranks)
    return jnp.argsort(aranks.astype(jnp.float32) * BIG - acrowd), aranks, acrowd


def run_nsga2(eval_fn: Callable, n_models: int, cfg: NSGAConfig,
              key=None, init_pop=None, valid_mask=None):
    """One client's GA: `run_nsga2_batched` at N=1, so a serial run and
    client i of a batch execute the same program.

    eval_fn: (P, M) 0/1 float -> (P, n_obj) objectives (maximized).
    `key` is this run's PRNG stream (defaults to PRNGKey(cfg.seed) for
    backwards compatibility). `valid_mask` (M,) 0/1 freezes masked slots
    at zero (padding models that have not arrived yet — DESIGN.md §4).

    Returns dict(pop, objs, ranks) of the final population. Entirely
    jittable; the caller closes eval_fn over acc/S (objectives.py).
    """
    if key is None:
        key = jax.random.PRNGKey(cfg.seed)
    out = run_nsga2_batched(
        lambda pop: eval_fn(pop[0])[None], n_models, cfg, key[None],
        init_pop=init_pop,
        valid_mask=None if valid_mask is None else valid_mask[None])
    return {k: v[0] for k, v in out.items()}


def run_nsga2_batched(eval_fn: Callable, n_models: int, cfg: NSGAConfig,
                      keys, init_pop=None, valid_mask=None):
    """N clients' GAs in lockstep. eval_fn: (N, P, M) -> (N, P, n_obj).

    `keys`: (N, 2) uint32 — one independent PRNG stream per client.
    `valid_mask`: optional (N, M) 0/1 per-client model-slot mask.

    The genetic operators are vmapped over the client axis; the two
    objective evaluations per generation see the full (N, P|2P, M)
    population, which is what lets a batched Pallas kernel score every
    client in a single launch (kernels/ensemble_fitness). Each
    generation: evaluate, rank, breed, then elitist (mu + lambda)
    survival over the combined 2P pool.
    """
    P, M, k = cfg.pop_size, n_models, cfg.k
    sub = jax.vmap(lambda kk: jax.random.split(kk, 3))(keys)  # (N, 3, 2)
    key_loop, k0, k1 = sub[:, 0], sub[:, 1], sub[:, 2]
    if valid_mask is None:
        pop = jax.vmap(lambda a, b: _init_population(a, b, P, M, k, None,
                                                     init_pop))(k0, k1)
    else:
        pop = jax.vmap(lambda a, b, vm: _init_population(a, b, P, M, k, vm,
                                                         init_pop))(k0, k1, valid_mask)

    def breed_one(pop_c, ranks_c, crowd_c, key_c, vm_c):
        return _breed(pop_c, ranks_c, crowd_c, key_c, cfg, vm_c)

    def gen(pop, keys_g):  # pop: (N, P, M); keys_g: (N, 2)
        objs = eval_fn(pop)                                   # (N, P, n_obj)
        ranks = jax.vmap(nondominated_rank)(objs)
        crowd = jax.vmap(crowding_distance)(objs, ranks)
        if valid_mask is None:
            child = jax.vmap(lambda p, r, c, kk: _breed(p, r, c, kk, cfg))(
                pop, ranks, crowd, keys_g)
        else:
            child = jax.vmap(breed_one)(pop, ranks, crowd, keys_g, valid_mask)
        allp = jnp.concatenate([pop, child], axis=1)          # (N, 2P, M)
        aobjs = eval_fn(allp)
        order = jax.vmap(lambda o: _survival_order(o)[0])(aobjs)
        pop = jnp.take_along_axis(allp, order[:, :P, None], axis=1)
        return pop, None

    gkeys = jax.vmap(lambda kk: jax.random.split(kk, cfg.generations))(key_loop)
    gkeys = jnp.swapaxes(gkeys, 0, 1)  # (G, N, 2)
    pop, _ = jax.lax.scan(gen, pop, gkeys)
    objs = eval_fn(pop)
    ranks = jax.vmap(nondominated_rank)(objs)
    return {"pop": pop, "objs": objs, "ranks": ranks}


def client_keys(seed: int, client_ids) -> jnp.ndarray:
    """Per-client PRNG streams: fold each client id into the base seed.
    Deterministic per (seed, client) regardless of batch composition, so
    sync and async drivers select identically for the same store state."""
    base = jax.random.PRNGKey(seed)
    ids = jnp.asarray(client_ids, jnp.uint32)
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(ids)
