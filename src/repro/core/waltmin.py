"""Step 3 of SMP-PCA: WAltMin — weighted alternating minimization (Alg 2).

Solves  min_{U,V} sum_{(i,j) in Omega} w_ij (e_i^T U V^T e_j - M~(i,j))^2,
w_ij = 1/q_hat_ij, on a static-shape COO sample. Spark's hash-partitioned ALS
becomes: per-row r x r normal equations built with ``segment_sum`` and solved
with a batched Cholesky-ish ``jnp.linalg.solve`` — the XLA-native equivalent.

Sample splitting (Alg 2 line 3): Omega is split into 2T+1 subsets; the t-th
half-iteration only *sees* subset 2t+1 / 2t+2 via masking (static shapes).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.types import LowRankFactors, SampleSet
from repro.core import sampling

_RIDGE = 1e-8


# ---------------------------------------------------------------------------
# COO helpers
# ---------------------------------------------------------------------------

def coo_matmat(rows: jax.Array, cols: jax.Array, vals: jax.Array,
               X: jax.Array, n_out: int) -> jax.Array:
    """(sparse (n_out, n_in)) @ X  where sparse[r, c] = vals, X: (n_in, p)."""
    contrib = vals[:, None] * X[cols]          # (nnz, p)
    return jax.ops.segment_sum(contrib, rows, num_segments=n_out)


def coo_rmatmat(rows: jax.Array, cols: jax.Array, vals: jax.Array,
                X: jax.Array, n_out: int) -> jax.Array:
    """(sparse)^T @ X."""
    contrib = vals[:, None] * X[rows]
    return jax.ops.segment_sum(contrib, cols, num_segments=n_out)


def coo_topr_svd(key: jax.Array, rows: jax.Array, cols: jax.Array,
                 vals: jax.Array, n1: int, n2: int, r: int,
                 n_iter: int = 8) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Randomized top-r SVD of a sparse (n1, n2) matrix via subspace iteration.

    Never materializes the dense matrix: only COO matvecs. Returns (U, s, V).
    """
    p = min(n2, r + 8)                         # oversampling
    G = jax.random.normal(key, (n2, p))
    Y = coo_matmat(rows, cols, vals, G, n1)    # (n1, p)

    def _body(_, Y):
        Q, _ = jnp.linalg.qr(Y)
        Z = coo_rmatmat(rows, cols, vals, Q, n2)   # (n2, p)
        Z, _ = jnp.linalg.qr(Z)
        return coo_matmat(rows, cols, vals, Z, n1)

    Y = jax.lax.fori_loop(0, n_iter, _body, Y)
    Q, _ = jnp.linalg.qr(Y)                    # (n1, p)
    Bt = coo_rmatmat(rows, cols, vals, Q, n2)  # (n2, p) = (Q^T S)^T
    Ub, s, Vt = jnp.linalg.svd(Bt.T, full_matrices=False)
    U = Q @ Ub[:, :r]
    return U, s[:r], Vt[:r].T


# ---------------------------------------------------------------------------
# WAltMin
# ---------------------------------------------------------------------------

def _trim_rows(U: jax.Array, norm_col: jax.Array, r: int) -> jax.Array:
    """Alg 2 step 6: zero rows whose norm exceeds 8 sqrt(r) ||A_i||/||A||_F,
    then re-orthonormalize. Guards the incoherence needed by Lemma C.2."""
    frob = jnp.sqrt(jnp.sum(norm_col ** 2))
    thresh = 8.0 * jnp.sqrt(r) * norm_col / jnp.maximum(frob, 1e-12)
    row_norm = jnp.linalg.norm(U, axis=1)
    keep = (row_norm <= jnp.maximum(thresh, 1e-12))[:, None]
    Ut = jnp.where(keep, U, 0.0)
    Q, _ = jnp.linalg.qr(Ut)
    return Q


def _ls_step(rows_from: jax.Array, cols_to: jax.Array, vals: jax.Array,
             w: jax.Array, F: jax.Array, n_to: int) -> jax.Array:
    """One half-iteration: solve for the ``cols_to`` side factor given F.

    For each target index t: G_t = sum w * F_i F_i^T ; b_t = sum w * val * F_i,
    over entries whose source index is i=rows_from and target t=cols_to.
    """
    r = F.shape[1]
    Fi = F[rows_from]                                   # (m, r)
    wv = (w * vals)[:, None] * Fi                       # (m, r)
    # the outer products are built flat, (m, r*r): an (m, r, r) array pads
    # its two minor dims to TPU tiles, (r, r) -> (8k, 128k) — 20x the bytes
    # at r = 10, more than a chip holds at the paper's default m
    ia, ib = jnp.repeat(jnp.arange(r), r), jnp.tile(jnp.arange(r), r)
    outer = w[:, None] * Fi[:, ia] * Fi[:, ib]          # (m, r*r)
    G = jax.ops.segment_sum(outer, cols_to,
                            num_segments=n_to).reshape(n_to, r, r)
    b = jax.ops.segment_sum(wv, cols_to, num_segments=n_to)
    # Two-scale Tikhonov: a 1e-6-relative per-row term for conditioning plus a
    # 1e-4-relative *global* floor. Rows that draw fewer than r samples under
    # Alg-2 splitting are underdetermined; the global floor damps their
    # null-space energy to O(1) instead of 1/eps, while biasing well-sampled
    # rows (whose Gram trace ~ the global mean) by only ~0.01%.
    tr = jnp.trace(G, axis1=1, axis2=2)[:, None, None]
    lam = 1e-6 * tr / r + 1e-4 * jnp.mean(tr) / r + _RIDGE
    G = G + lam * jnp.eye(r)
    return jnp.linalg.solve(G, b[..., None])[..., 0]    # (n_to, r)


def _waltmin_impl(key: jax.Array, samples: SampleSet, values: jax.Array,
                  n1: int, n2: int, r: int, T: int,
                  norm_A: jax.Array | None, use_splits: bool,
                  scan: bool) -> LowRankFactors:
    """One body for both execution modes: ``scan=True`` runs the T iteration
    pairs as one ``lax.scan`` (the jitted path), ``scan=False`` as a Python
    loop of eager dispatches (the EstimationEngine's reference oracle). The
    iteration driver is the ONLY thing that differs — weights, masks, keys,
    init, and the final solve are shared, which is what keeps the
    cross-backend parity contract a property of the code rather than of
    hand-synchronized copies."""
    w_all = jnp.where(samples.mask, 1.0 / jnp.maximum(samples.q_hat, 1e-12), 0.0)
    vals = jnp.where(samples.mask, values, 0.0)
    if norm_A is None:
        norm_A = jnp.ones((n1,))

    k_split, k_svd = jax.random.split(key)
    if use_splits:
        subset = sampling.split_omega(k_split, samples, 2 * T + 1)
    else:
        subset = jnp.zeros((samples.m,), jnp.int32)

    def _wmask(s):
        if not use_splits:
            return w_all
        # splits partition Omega; rescale q_hat by subset fraction
        return jnp.where(subset == s, w_all * (2 * T + 1), 0.0)

    # --- init: SVD of R_Omega0(M~), trim, orthonormalize -------------------
    w0 = _wmask(0)
    U0, _, _ = coo_topr_svd(k_svd, samples.rows, samples.cols, w0 * vals,
                            n1, n2, r)
    U = _trim_rows(U0, norm_A, r)

    # --- alternating half-iterations ---------------------------------------
    # Each half-step solves the weighted LS for one side given the *column
    # space* of the other; orthonormalizing the carried factor between steps
    # removes the scale drift that makes raw ALS diverge in f32 (only the
    # span matters — the final V solve restores a consistent scaled pair).
    def _half_pair(U, t):
        V = _ls_step(samples.rows, samples.cols, vals, _wmask(2 * t + 1), U, n2)
        Vq, _ = jnp.linalg.qr(V)
        Unew = _ls_step(samples.cols, samples.rows, vals, _wmask(2 * t + 2),
                        Vq, n1)
        Uq, _ = jnp.linalg.qr(Unew)
        return Uq

    if scan:
        U_final, _ = jax.lax.scan(lambda U, t: (_half_pair(U, t), None),
                                  U, jnp.arange(T))
    else:
        U_final = U
        for t in range(T):
            U_final = _half_pair(U_final, t)
    # final V solve against the last (orthonormal) U: consistent scaled pair
    V_final = _ls_step(samples.rows, samples.cols, vals, _wmask(2 * T - 1),
                       U_final, n2)
    return LowRankFactors(U_final, V_final)


@functools.partial(
    jax.jit, static_argnames=("n1", "n2", "r", "T", "use_splits"))
def waltmin(key: jax.Array, samples: SampleSet, values: jax.Array,
            n1: int, n2: int, r: int, T: int,
            norm_A: jax.Array | None = None,
            use_splits: bool = True) -> LowRankFactors:
    """Algorithm 2. ``values`` are M~ on Omega (or exact entries for LELA).

    norm_A: column norms used by the trim step (falls back to uniform).
    use_splits=False reuses all samples every iteration (practical mode, what
    the paper's Spark code does; splits are for the analysis).
    """
    return _waltmin_impl(key, samples, values, n1, n2, r, T, norm_A,
                         use_splits, scan=True)


def waltmin_reference(key: jax.Array, samples: SampleSet, values: jax.Array,
                      n1: int, n2: int, r: int, T: int,
                      norm_A: jax.Array | None = None,
                      use_splits: bool = True) -> LowRankFactors:
    """Algorithm 2 as written on the page: T Python-level iteration pairs,
    every half-step dispatched eagerly (no jit, no scan) — the
    EstimationEngine's ``backend='reference'`` oracle for the jitted scan
    loop."""
    return _waltmin_impl(key, samples, values, n1, n2, r, T, norm_A,
                         use_splits, scan=False)
