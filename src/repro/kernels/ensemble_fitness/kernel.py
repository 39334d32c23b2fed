"""Pallas TPU kernel: score a whole NSGA-II population.

The paper evaluates P x G candidate ensembles per client sequentially on
CPU; on TPU the population is scored as blocked matmuls. Grid step
(n, i) keeps client n's i-th (BLOCK_P, M) chromosome tile, its (1, M)
accuracy and diag(S) rows and its (M, M) similarity Gram matrix resident
in VMEM (M <= ~1500 comfortably fits: M^2 fp32 @ M=1024 is 4 MB).

  strength  = (C @ acc) / k
  diversity = 1 - (rowsum((C @ S) * C) - C @ diag(S)) / (k (k-1))

The math is `core.objectives.fitness_terms`, the same function the jnp
path runs, so both paths score a chromosome identically. It yields
(BLOCK_P, 1) columns; the kernel packs them into lanes 0 and 1 of one
(BLOCK_P, 128) tile and transposes it, so the output block is a
lane-dense (1, 2, BLOCK_P) slab of an (N, 2, Pp) array. The last two
dimensions of every block equal the array's or divide by (8, 128), as
Mosaic requires for any client count N.

Two entry points:

  ensemble_fitness_batched  — N clients in ONE launch, grid
                              (N, Pp // BLOCK_P). This is what
                              `select_ensembles`'s vmapped NSGA-II calls
                              with use_kernel=True.
  ensemble_fitness          — one client: the batched kernel at N=1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.objectives import fitness_terms

BLOCK_P = 128
LANES = 128


def _kernel(pop_ref, acc_ref, S_ref, diag_ref, out_ref):
    # blocks carry a leading singleton client dim: (1, BLOCK_P, M) etc.
    strength, diversity = fitness_terms(pop_ref[0], acc_ref[0], S_ref[0],
                                        diag_ref[0])
    lane = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_P, LANES), 1)
    packed = jnp.where(lane == 0, strength,
                       jnp.where(lane == 1, diversity, 0.0))
    out_ref[0] = packed.T[:2]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ensemble_fitness_batched(pop, acc, S, interpret: bool = True):
    """pop: (N, P, M) f32; acc: (N, M); S: (N, M, M) ->
    (strength (N, P), diversity (N, P)) — one launch for all N clients."""
    N, P, M = pop.shape
    pad = (-P) % BLOCK_P
    if pad:
        pop = jnp.pad(pop, ((0, 0), (0, pad), (0, 0)))
    Pp = pop.shape[1]
    Sf = S.astype(jnp.float32)
    diag = jnp.diagonal(Sf, axis1=1, axis2=2)  # (N, M), host-side precompute
    out = pl.pallas_call(
        _kernel,
        grid=(N, Pp // BLOCK_P),
        in_specs=[
            pl.BlockSpec((1, BLOCK_P, M), lambda n, i: (n, i, 0)),
            pl.BlockSpec((1, 1, M), lambda n, i: (n, 0, 0)),
            pl.BlockSpec((1, M, M), lambda n, i: (n, 0, 0)),
            pl.BlockSpec((1, 1, M), lambda n, i: (n, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 2, BLOCK_P), lambda n, i: (n, 0, i)),
        out_shape=jax.ShapeDtypeStruct((N, 2, Pp), jnp.float32),
        interpret=interpret,
    )(pop.astype(jnp.float32), acc.astype(jnp.float32)[:, None, :],
      Sf, diag[:, None, :])
    return out[:, 0, :P], out[:, 1, :P]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ensemble_fitness(pop, acc, S, interpret: bool = True):
    """pop: (P, M) f32; acc: (M,); S: (M, M) -> (strength, diversity)."""
    strength, diversity = ensemble_fitness_batched(
        pop[None], acc[None], S[None], interpret=interpret)
    return strength[0], diversity[0]
