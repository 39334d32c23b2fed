"""Public wrapper for the batched ensemble_fitness kernel: compiled by
Mosaic on a TPU, run by the Pallas interpreter on any other backend.
"""
from __future__ import annotations

import jax

from .kernel import ensemble_fitness_batched as _kernel_call_batched


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def ensemble_fitness_batched(pop, acc, S):
    return _kernel_call_batched(pop, acc, S, interpret=_interpret())
