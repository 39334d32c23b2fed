"""NSGA-II (Deb et al. 2002), fully vectorized in JAX.

The whole genetic loop is a single `lax.scan` over generations. The
initial population is evaluated, ranked and crowded once; each generation
then breeds P children from the carried ranks and crowding (tournament
selection / uniform crossover / bit-flip mutation / exact-k repair as
vectorized bit ops), evaluates only the children with two matmuls (see
objectives.py), and ranks the 2P pool of survivors and children once:
dominance is P x P boolean algebra, and the fronts are peeled with a
`while_loop` only until P members have one (the front that crosses P is
peeled whole). Crowding is assigned within the pool's fronts, and the
first P by rank, then crowding, survive with their objectives, pool ranks
and pool crowding, which the next tournament reads. A survivor's pool rank
is its rank among the survivors, because every front before the last one
admitted survives whole. That holds only if the survival order is exact:
both orders are one `lax.sort` over two keys (rank, value for crowding;
rank, then crowding descending, for survival). The float32 composite keys
they replace, `rank * 1e9 + value` and `rank * 1e9 - crowding`, lost the
value at every rank >= 1 (float32's spacing near 1e9 is 64) and put a
front's boundary members (crowding 1e9 an objective) ahead of the front
before it. On TPU this turns the paper's per-client CPU hot loop into an
MXU-shaped batch job.

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

The batched run carries device scopes in its op names (DESIGN.md §11):
`ga`, with `ga/init`, `ga/eval` (every objective evaluation), `ga/rank`
(front peeling and crowding), `ga/breed` and `ga/survive`, and returns
each client's peeling sweeps (`rank_sweeps`).
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


def _peel(objs, stop_at=None):
    """Front peeling: (ranks (P,), sweeps). Each sweep gives the members
    that no remaining member dominates the next rank. With `stop_at`,
    peeling ends once at least `stop_at` members have a rank (the front
    that crosses it is peeled whole); the rest keep the sentinel P."""
    P = objs.shape[0]
    dom = dominance(objs)  # (P, P)

    def cond(state):
        ranks, remaining, r = state
        go = jnp.any(remaining) & (r < P)
        if stop_at is not None:
            go &= P - jnp.sum(remaining) < stop_at
        return go

    def body(state):
        ranks, remaining, r = state
        dominated = jnp.any(dom & remaining[:, None] & remaining[None, :], axis=0)
        front = remaining & ~dominated
        ranks = jnp.where(front, r, ranks)
        return ranks, remaining & ~front, r + 1

    ranks0 = jnp.full((P,), P, jnp.int32)
    ranks, _, sweeps = jax.lax.while_loop(
        cond, body, (ranks0, jnp.ones((P,), bool), jnp.int32(0)))
    return ranks, sweeps


def nondominated_rank(objs, stop_at=None):
    """(P,) rank per individual (0 = Pareto front) by iterative peeling.
    With `stop_at`, only the fronts holding the first `stop_at` members
    are peeled; members left unranked keep the sentinel P."""
    return _peel(objs, stop_at)[0]


def _unpermute(sorted_vals, order):
    """`out[order[j]] = sorted_vals[j]`, as a one-hot select and sum: exact
    (one nonzero term per output) and free of the scatter a TPU runs one
    index at a time."""
    idx = jnp.arange(order.shape[0], dtype=order.dtype)
    return jnp.sum(jnp.where(order[None, :] == idx[:, None],
                             sorted_vals[None, :], 0.0), axis=1)


def crowding_distance(objs, ranks):
    """(P,) crowding distance computed within each rank front. Per
    objective the members are put in the exact (rank, value) order, ties
    by index, with one `lax.sort` over the two keys; an interior member
    adds the gap between its neighbours over the objective's range, a
    front's first and last get BIG."""
    P, n_obj = objs.shape
    idx = jnp.arange(P, dtype=jnp.int32)
    dist = jnp.zeros((P,), jnp.float32)
    for m in range(n_obj):
        v = objs[:, m]
        r_sorted, v_sorted, order = jax.lax.sort((ranks, v, idx), num_keys=2)
        same = r_sorted[1:] == r_sorted[:-1]
        prev_ok = jnp.concatenate([jnp.array([False]), same])
        next_ok = jnp.concatenate([same, jnp.array([False])])
        prev_v = jnp.concatenate([v_sorted[:1], v_sorted[:-1]])
        next_v = jnp.concatenate([v_sorted[1:], v_sorted[-1:]])
        span = jnp.maximum(jnp.max(v) - jnp.min(v), 1e-12)
        contrib = jnp.where(prev_ok & next_ok, (next_v - prev_v) / span, BIG)
        dist = dist + _unpermute(contrib, order)
    return dist


def survival_order(ranks, crowd, objs):
    """The pool in survival order: rank ascending, then crowding
    descending, ties by index, by one exact `lax.sort` over the two keys,
    so every member of a lower front comes before any member of a higher
    one. Returns (order, ranks, crowd, objs), each in that order: the sort
    carries the survivors' state along, where a TPU would gather it one
    element at a time."""
    idx = jnp.arange(ranks.shape[0], dtype=jnp.int32)
    ranks, neg, order, *cols = jax.lax.sort(
        (ranks, -crowd, idx) + tuple(objs.T), num_keys=2)
    return order, ranks, -neg, jnp.stack(cols, axis=1)


def _tournament(key, ranks, crowd, n):
    """Binary tournament: lower rank wins, ties by higher crowding."""
    P = ranks.shape[0]
    idx = jax.random.randint(key, (2, n), 0, P)
    a, b = idx[0], idx[1]
    a_better = (ranks[a] < ranks[b]) | ((ranks[a] == ranks[b]) & (crowd[a] > crowd[b]))
    return jnp.where(a_better, a, b)


def keep_top_k(score, k: int):
    """(P, M) float 0/1 marking the k largest scores of each row. Scores
    equal to the k-th largest are kept leftmost first, so a tie there
    never gives a row more than k ones."""
    thresh = -jnp.sort(-score, axis=1)[:, k - 1:k]  # k-th largest
    above = score > thresh
    tied = score == thresh
    room = k - above.sum(axis=1, keepdims=True)
    return (above | (tied & (jnp.cumsum(tied, axis=1) <= room))
            ).astype(jnp.float32)


def repair_k(pop_f, key, k: int, valid_mask=None):
    """Force exactly k ones per row: keep set bits with priority, fill the
    rest randomly. pop_f: (P, M) float 0/1. With `valid_mask` (M,) 0/1,
    masked-out slots score below every valid slot and can never be set —
    rows end up with min(k, #valid) ones. The float32 noise ties about
    once in a million rows (2 + noise has 2^22 values), hence
    `keep_top_k`'s tie rule."""
    P, M = pop_f.shape
    noise = jax.random.uniform(key, (P, M))
    score = pop_f * 2.0 + noise  # existing bits rank above absent ones
    if valid_mask is not None:
        score = score - (1.0 - valid_mask) * 8.0
    rep = keep_top_k(score, k)
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


def _rank_crowd(objs, stop_at=None):
    """(N, P, n_obj) -> per-client (ranks, crowding, peeling sweeps),
    device scope `rank`."""
    with jax.named_scope("rank"):
        ranks, sweeps = jax.vmap(lambda o: _peel(o, stop_at))(objs)
        return ranks, jax.vmap(crowding_distance)(objs, ranks), sweeps


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

    The genetic operators are vmapped over the client axis; each
    objective evaluation sees the full (N, P, M) population, which is
    what lets a batched Pallas kernel score every client in a single
    launch (kernels/ensemble_fitness). The initial population is
    evaluated, ranked and crowded once; each generation then breeds from
    the carried ranks and crowding, evaluates only the P children, ranks
    the 2P pool until P members have a front, and keeps the first P by
    rank, then crowding (elitist (mu + lambda) survival). The survivors'
    objectives, pool ranks and pool crowding are carried on: a survivor's
    rank in the pool is its rank among the survivors, since every front
    before the last one admitted survives whole.

    Returns dict(pop, objs, ranks, rank_sweeps): the final population,
    its objectives and ranks, and each client's peeling sweeps.
    """
    P, M, k = cfg.pop_size, n_models, cfg.k
    with jax.named_scope("ga"):
        def evaluate(pop):
            with jax.named_scope("eval"):
                return eval_fn(pop)

        with jax.named_scope("init"):
            sub = jax.vmap(lambda kk: jax.random.split(kk, 3))(keys)
            key_loop, k0, k1 = sub[:, 0], sub[:, 1], sub[:, 2]
            if valid_mask is None:
                pop = jax.vmap(lambda a, b: _init_population(
                    a, b, P, M, k, None, init_pop))(k0, k1)
            else:
                pop = jax.vmap(lambda a, b, vm: _init_population(
                    a, b, P, M, k, vm, init_pop))(k0, k1, valid_mask)
            gkeys = jax.vmap(lambda kk: jax.random.split(
                kk, cfg.generations))(key_loop)
            gkeys = jnp.swapaxes(gkeys, 0, 1)  # (G, N, 2)

        def breed_one(pop_c, ranks_c, crowd_c, key_c, vm_c):
            return _breed(pop_c, ranks_c, crowd_c, key_c, cfg, vm_c)

        def start(args):
            """Step 0: the initial population, ranked whole."""
            _, _, _, _, sweeps, child, cobjs = args
            ranks, crowd, n = _rank_crowd(cobjs)
            return child, cobjs, ranks, crowd, sweeps + n

        def survive(args):
            """Steps 1..G: the first P of the 2P pool."""
            pop, objs, _, _, sweeps, child, cobjs = args
            with jax.named_scope("survive"):
                allp = jnp.concatenate([pop, child], axis=1)  # (N, 2P, M)
                aobjs = jnp.concatenate([objs, cobjs], axis=1)
            aranks, acrowd, n = _rank_crowd(aobjs, stop_at=P)
            with jax.named_scope("survive"):     # rank asc, crowding desc
                order, aranks, acrowd, aobjs = jax.vmap(survival_order)(
                    aranks, acrowd, aobjs)
                return (jnp.take_along_axis(allp, order[:, :P, None], axis=1),
                        aobjs[:, :P], aranks[:, :P], acrowd[:, :P], sweeps + n)

        def gen(state, xs):  # pop: (N, P, M); keys_g: (N, 2)
            # Step 0 evaluates the initial population and steps 1..G the
            # children, all at this one call: a chromosome met twice must
            # score the same, and XLA may round an evaluation fused
            # elsewhere otherwise (a duplicate would then dominate its
            # twin).
            g, keys_g = xs
            pop, objs, ranks, crowd, sweeps = state
            with jax.named_scope("breed"):
                if valid_mask is None:
                    child = jax.vmap(
                        lambda p, r, c, kk: _breed(p, r, c, kk, cfg))(
                            pop, ranks, crowd, keys_g)
                else:
                    child = jax.vmap(breed_one)(pop, ranks, crowd, keys_g,
                                                valid_mask)
                child = jnp.where(g == 0, pop, child)
            cobjs = evaluate(child)                           # (N, P, n_obj)
            return jax.lax.cond(g == 0, start, survive,
                                state + (child, cobjs)), None

        N = pop.shape[0]
        objs = jax.eval_shape(eval_fn, pop)
        state = (pop, jnp.zeros(objs.shape, objs.dtype),
                 jnp.zeros((N, P), jnp.int32), jnp.zeros((N, P), jnp.float32),
                 jnp.zeros((N,), jnp.int32))
        step_keys = jnp.concatenate(   # step 0 breeds nothing it keeps
            [jnp.zeros((1,) + gkeys.shape[1:], gkeys.dtype), gkeys])
        (pop, objs, ranks, _, sweeps), _ = jax.lax.scan(
            gen, state, (jnp.arange(cfg.generations + 1), step_keys))
        return {"pop": pop, "objs": objs, "ranks": ranks,
                "rank_sweeps": sweeps}


def client_keys(seed: int, client_ids) -> jnp.ndarray:
    """Per-client PRNG streams: fold each client id into the base seed.
    Deterministic per (seed, client) regardless of batch composition, so
    sync and async drivers select identically for the same store state."""
    base = jax.random.PRNGKey(seed)
    ids = jnp.asarray(client_ids, jnp.uint32)
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(ids)
