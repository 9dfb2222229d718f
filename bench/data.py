"""Inputs made on the device from ``--seed``: a correlated pair with a
planted decaying spectrum.

``A = G diag(i^-DECAY)``, ``B = A + SIGMA * G' diag(i^-DECAY)`` with G, G'
standard Gaussian, as the repository's ``chip_smoke.py`` makes it: the top-r
part of ``A^T B`` stands well above the noise. The construction and its
constants are this benchmark's choice, not a setting of the source paper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number up to 2**64: both
    32-bit halves are folded in, so large seeds do not overflow."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


@functools.partial(jax.jit, static_argnames=("count", "rows", "n1", "n2",
                                             "decay", "sigma"))
def make_pool(key, *, count: int, rows: int, n1: int, n2: int,
              decay: float, sigma: float):
    """``count`` independent (rows, n1) / (rows, n2) row blocks of the pair,
    in one jitted call; returns (tuple of A blocks, tuple of B blocks)."""
    if n1 != n2:
        raise ValueError("the planted pair needs n1 == n2")
    scale = 1.0 / jnp.arange(1.0, n1 + 1.0) ** decay
    As, Bs = [], []
    for i in range(count):
        k_a, k_b = jax.random.split(jax.random.fold_in(key, i))
        A = jax.random.normal(k_a, (rows, n1)) * scale
        As.append(A)
        Bs.append(A + sigma * jax.random.normal(k_b, (rows, n2)) * scale)
    return tuple(As), tuple(Bs)
