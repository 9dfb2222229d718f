"""The plain reference for sparse bag-of-words chunks.

It imports nothing of the program and takes nothing the program made: it
works from the benchmark's own chunks ``(rows, cols, vals)`` (a repeated
``(row, col)`` adds) under the key contract ``bench/reference.py`` states,
and rebuilds the one-pass summary of the chunks fed, in the window's order:

* the sketch at the checked columns only: for each chunk fed it rebuilds
  the projection of the chunk's global row ids and takes ``P^T`` times the
  chunk's checked columns, densified, at ``passes`` (6 = f32 at HIGHEST,
  3 = the bf16x3 control), accumulating in f32;
* every column norm and the whole probe block: each distinct chunk's
  summands in float64 on the host, once (they do not depend on the row
  offset), then added in f32 for each chunk fed, in order, as the
  streaming monoid adds them.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse

from reference import dot, probe_omega, projection

_TN = (((0,), (0,)), ((), ()))      # X^T Y


def host_chunks(chunks: Sequence[tuple]) -> list:
    """The chunks as host ``(rows, cols, vals)`` arrays."""
    return [tuple(np.asarray(a) for a in c) for c in jax.device_get(chunks)]


def checked_columns(chunks: Sequence[tuple], n: int, seed: int, salt: int,
                    count: int = 512, hot: int = 64) -> np.ndarray:
    """The ``hot`` columns that the most rows hold, and ``count - hot``
    others drawn from the seed, sorted: a uniform sample alone would be
    almost all rare words."""
    freq = sum(np.bincount(cols[vals != 0], minlength=n)
               for _, cols, vals in chunks)
    top = np.argsort(-freq, kind="stable")[:hot]
    rest = np.setdiff1d(np.arange(n), top)
    rng = np.random.default_rng([seed, salt])
    drawn = rng.choice(rest, size=min(count - hot, rest.size), replace=False)
    return np.sort(np.concatenate([top, drawn]))


def dense_columns(chunk: tuple, t: int, cols_sel: np.ndarray) -> np.ndarray:
    """(t, len(cols_sel)) f32: the chunk's columns ``cols_sel``, dense."""
    rows, cols, vals = chunk
    pos = np.minimum(np.searchsorted(cols_sel, cols), cols_sel.size - 1)
    hit = cols_sel[pos] == cols
    out = np.zeros((t, cols_sel.size), np.float32)
    np.add.at(out, (rows[hit], pos[hit]), vals[hit])
    return out


def _csr(chunk: tuple, t: int, n: int):
    rows, cols, vals = chunk
    return scipy.sparse.csr_matrix(
        (vals.astype(np.float64), (rows, cols)), shape=(t, n))


@functools.partial(jax.jit, static_argnames=("k", "passes"))
def _chunk_step(acc, key, offset, A_cols, B_cols, side, *, k: int,
                passes: int):
    """One chunk fed: its sketch columns, and its norms and probe summand
    (``side``) added in f32, as the streaming monoid adds them."""
    P = projection(key, offset + jnp.arange(A_cols.shape[0],
                                            dtype=jnp.int32), k)
    return (acc[0] + dot(P, A_cols, _TN, passes),
            acc[1] + dot(P, B_cols, _TN, passes),
            *(a + b for a, b in zip(acc[2:], side)))


def chunk_side(A: tuple, B: tuple, t: int, shape: tuple, omega):
    """(squared column norms of A and of B, ``A^T (B omega)``) of one chunk
    pair, in float64."""
    same = B is A
    A = _csr(A, t, shape[0])
    B = A if same else _csr(B, t, shape[1])
    return (np.asarray(A.multiply(A).sum(axis=0)).ravel(),
            np.asarray(B.multiply(B).sum(axis=0)).ravel(),
            A.T @ (B @ omega))


def stream_summary(key, pool_A: Sequence[tuple], pool_B: Sequence[tuple],
                   plan: Sequence[tuple], t: int, shape: tuple, k: int,
                   p: int, cols_A, cols_B, passes: int = 6
                   ) -> Dict[str, np.ndarray]:
    """The summary of the chunks ``(pool index, first global row)`` of
    ``plan`` in order: sketch columns ``cols_A``/``cols_B``, every column
    norm, the whole probe block and the row count. ``pool_*`` are host
    chunks of ``t`` rows; ``shape = (n1, n2)``. Each chunk's summands are
    added in f32, chunk by chunk."""
    omega = np.asarray(probe_omega(key, shape[1], p), np.float64)
    fed = sorted({idx for idx, _ in plan})
    sub = {idx: (jnp.asarray(dense_columns(pool_A[idx], t, cols_A)),
                 jnp.asarray(dense_columns(pool_B[idx], t, cols_B)))
           for idx in fed}
    side = {idx: tuple(jnp.asarray(x, jnp.float32) for x in chunk_side(
        pool_A[idx], pool_B[idx], t, shape, omega)) for idx in fed}
    acc = (jnp.zeros((k, len(cols_A)), jnp.float32),
           jnp.zeros((k, len(cols_B)), jnp.float32),
           jnp.zeros((shape[0],), jnp.float32),
           jnp.zeros((shape[1],), jnp.float32),
           jnp.zeros((shape[0], p), jnp.float32))
    for idx, offset in plan:
        acc = _chunk_step(acc, key, jnp.int32(offset), *sub[idx], side[idx],
                          k=k, passes=passes)
    acc_a, acc_b, na2, nb2, probes = jax.device_get(acc)
    return {"A_sketch": acc_a, "B_sketch": acc_b, "norm_A": np.sqrt(na2),
            "norm_B": np.sqrt(nb2), "probes": probes, "rows": t * len(plan)}
