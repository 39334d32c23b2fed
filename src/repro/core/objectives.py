"""FedPAE ensemble objectives: strength and diversity.

TPU-native recast (DESIGN.md §5): from the bench's prediction tensor
`probs` (M models x V validation samples x C classes) we precompute
  acc  in R^M      — per-model validation accuracy            (strength)
  S    in R^{MxM}  — pairwise prediction-similarity Gram matrix (diversity)
after which scoring a whole NSGA-II population C in {0,1}^{PxM} is two
matmuls (see kernels/ensemble_fitness for the Pallas version):
  strength(c)  = (C @ acc) / k
  diversity(c) = 1 - (c^T S c - sum_i c_i S_ii) / (k (k-1))
The pairwise similarity follows Pang et al. (2019): mean inner product of
L2-normalised predicted-probability vectors (1 = identical predictions,
0 = orthogonal), so `diversity` is the mean pairwise de-correlation among
ensemble members.

Every float32 contraction here runs at `F32` (HIGHEST) precision: on a TPU
the default precision feeds a float32 matmul bf16 inputs, which would
round `acc` and `S` to ~3 significant digits; on a CPU it changes nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jax.lax.Precision.HIGHEST


def member_accuracy(probs, labels):
    """probs: (M, V, C); labels: (V,) with -1 = padding -> (M,) accuracy."""
    valid = labels >= 0
    nv = jnp.maximum(jnp.sum(valid), 1)
    pred = jnp.argmax(probs, axis=-1)
    hit = (pred == labels[None, :]) & valid[None, :]
    return jnp.sum(hit.astype(jnp.float32), axis=-1) / nv


def similarity_matrix(probs, labels=None):
    """probs: (M, V, C) -> (M, M) mean pairwise normalized inner product
    over valid (non-padding) samples."""
    p = probs.astype(jnp.float32)
    p = p / (jnp.linalg.norm(p, axis=-1, keepdims=True) + 1e-12)
    if labels is not None:
        valid = (labels >= 0).astype(jnp.float32)
        p = p * valid[None, :, None]
        nv = jnp.maximum(jnp.sum(valid), 1.0)
    else:
        nv = probs.shape[1]
    # S[i,j] = mean_v <p_i(v), p_j(v)>
    return jnp.einsum("mvc,nvc->mn", p, p, precision=F32) / nv


def fitness_terms(c, acc, S, diag):
    """The fitness math, shared by the jnp path and the Pallas kernel.

    c: (P, M) 0/1 float32; acc, diag: (1, M) (diag = diag(S)); S: (M, M)
    -> (strength (P, 1), diversity (P, 1)). Per-chromosome sums are
    elementwise products reduced over M, not matrix-vector products: a
    compiler rewrites a matvec differently for a batch of one client and
    a batch of many, and the GA's exact ties between equal-strength
    ensembles then break differently. Both paths run this one function,
    so a client's selection is the same alone, in any batch, and with
    or without the kernel (on the CPU: tests/test_engine.py)."""
    k = jnp.sum(c, axis=1, keepdims=True)
    strength = jnp.sum(c * acc, axis=1, keepdims=True) / jnp.maximum(k, 1.0)
    cs = jnp.dot(c, S, precision=F32, preferred_element_type=jnp.float32)
    quad = jnp.sum(cs * c, axis=1, keepdims=True)
    self_sim = jnp.sum(c * diag, axis=1, keepdims=True)
    pairs = jnp.maximum(k * (k - 1.0), 1.0)
    return strength, 1.0 - (quad - self_sim) / pairs


def population_objectives(pop, acc, S):
    """pop: (P, M) 0/1 float; acc: (M,); S: (M, M).
    Returns (strength (P,), diversity (P,)). Ensemble size k per row."""
    strength, diversity = fitness_terms(pop.astype(jnp.float32),
                                        acc[None, :], S,
                                        jnp.diagonal(S)[None, :])
    return strength[:, 0], diversity[:, 0]


def ensemble_accuracy(pop, probs, labels):
    """Overall accuracy of each candidate ensemble (mean-prob vote).
    pop: (P, M); probs: (M, V, C); labels: (V,) -1=pad -> (P,)."""
    pop = pop.astype(jnp.float32)
    valid = labels >= 0
    nv = jnp.maximum(jnp.sum(valid), 1)
    p = probs.astype(jnp.float32)
    # contract over a 2D (M, V·C) view — the free reshape keeps XLA:CPU
    # from transpose-copying the prediction tensor before the matmul
    votes = jnp.dot(pop, p.reshape(p.shape[0], -1), precision=F32).reshape(
        pop.shape[0], p.shape[1], p.shape[2])
    pred = jnp.argmax(votes, axis=-1)  # (P, V)
    hit = (pred == labels[None, :]) & valid[None, :]
    return jnp.sum(hit.astype(jnp.float32), axis=-1) / nv
