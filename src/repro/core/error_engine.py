"""ErrorEngine — a-posteriori sketch-quality estimation from held-out probes.

The paper's central idea is that retaining *extra* summary information beyond
the sketches (the exact column norms) buys a better estimate of A^T B. This
module pushes the same idea one step further, following Tropp et al.,
"Practical sketching algorithms for low-rank matrix approximation"
(1609.00048): retain ``p`` extra held-out probe columns

    probes = (A^T B) @ Omega,    Omega (n2, p) standard Gaussian,

accumulated in the same single pass (``probes = sum_rows A_row^T (B_row
Omega)`` — linear in the rows, so the probe block rides the existing
streaming/merge monoid unchanged), and use them *after* estimation to
measure how good the factors actually are:

* ``estimate_error(summary, factors)`` — for Gaussian ``w``,
  ``E ||(M - UV^T) w||^2 = ||M - UV^T||_F^2`` exactly, so the p probes give
  an unbiased Frobenius-residual estimate with a confidence interval, plus
  a spectral-norm proxy (``max_j ||R w_j|| / ||w_j||``, a lower-bound
  estimator of ``||R||_2``);
* ``adaptive_rank(summary, tol, r_max)`` — the smallest rank whose
  *estimated* relative error meets ``tol``. ONE factorization of the
  rescaled sketch product is computed and ONE probe projection is reused
  across every candidate rank (the per-rank error curve is a cumulative
  sum; the rank search runs over that precomputed host-side curve), never
  one factorization per candidate.

Randomness contract: ``Omega`` is a pure function of the summary key —
``normal(fold_in(fold_in(key, _PROBE_TAG_0), _PROBE_TAG_1), (n2, p))`` — a
two-level fold that cannot collide with the engine's single-level per-row
``fold_in(key, i)`` derivations, so every backend, every chunking, and every
merge order sees the *identical* held-out probes (golden-tested in
tests/core/test_key_contract.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimator
from repro.core.types import ErrorEstimate, LowRankFactors, SketchSummary

# "prob"/"e!" — the two-level fold that reserves the probe key subtree
_PROBE_TAG_0 = 0x70726F62
_PROBE_TAG_1 = 0x6521

_EPS = 1e-12

# 97.5% normal quantile: the default two-sided 95% confidence interval
_Z95 = 1.959964

# f32 products stay f32 on a TPU (its default matmul rounds inputs to bf16,
# which the residual's cancellation against the probe block cannot absorb)
_HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# The probe block (single-pass accumulation primitives)
# ---------------------------------------------------------------------------

def probe_key(key: jax.Array) -> jax.Array:
    """The reserved probe subtree of the summary key (two-level fold)."""
    return jax.random.fold_in(jax.random.fold_in(key, _PROBE_TAG_0),
                              _PROBE_TAG_1)


def probe_omega(key: jax.Array, n2: int, p: int) -> jax.Array:
    """(n2, p) standard-Gaussian held-out probes — a pure function of the
    summary key, identical on every backend/chunking/merge order."""
    return jax.random.normal(probe_key(key), (n2, p))


def probe_contribution(omega: jax.Array, A_chunk: jax.Array,
                       B_chunk: jax.Array,
                       precision: Optional[str] = None) -> jax.Array:
    """One row chunk's probe-block summand: ``A_chunk^T (B_chunk @ omega)``.

    (t, n1)^T @ ((t, n2) @ (n2, p)) with f32 accumulation regardless of the
    input dtype — the exact float ops the streaming update and the one-shot
    probe pass share, which is what the bit-parity contract rests on.
    A zero-row chunk contributes exact zeros (the monoid identity).
    """
    from repro.core.summary_engine import _cast
    Ac, Bc = _cast(A_chunk, precision), _cast(B_chunk, precision)
    Bw = jax.lax.dot_general(Bc, _cast(omega, precision).astype(Bc.dtype),
                             dimension_numbers=(((1,), (0,)), ((), ())),
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32)
    return jax.lax.dot_general(Ac, Bw.astype(Ac.dtype),
                               dimension_numbers=(((0,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block", "precision"))
def probe_pass(omega: jax.Array, A: jax.Array, B: jax.Array, *,
               block: int = 1024,
               precision: Optional[str] = None) -> jax.Array:
    """(n1, p) probe block over the whole in-memory pair: a ``lax.scan``
    over row blocks mirroring the scan backend's block structure (zero-padded
    trailing block), so sequential streamed ingestion at chunk ``block`` is
    bit-identical to this one-shot pass."""
    d, n1 = A.shape
    n2 = B.shape[1]
    pad = (-d) % block
    Ablk = jnp.pad(A, ((0, pad), (0, 0))).reshape(-1, block, n1)
    Bblk = jnp.pad(B, ((0, pad), (0, 0))).reshape(-1, block, n2)

    def _body(acc, ab):
        Ab, Bb = ab
        return acc + probe_contribution(omega, Ab, Bb, precision), None

    init = jnp.zeros((n1, omega.shape[1]), jnp.float32)
    acc, _ = jax.lax.scan(_body, init, (Ablk, Bblk))
    return acc


def attach_probes(summary: SketchSummary, key: jax.Array, A: jax.Array,
                  B: jax.Array, p: int, *, block: int = 1024,
                  precision: Optional[str] = None) -> SketchSummary:
    """Retain ``p`` held-out probes on an existing summary (the backend-
    independent stage ``build_summary(..., probes=p)`` runs after dispatch)."""
    omega = probe_omega(key, B.shape[-1], p)
    return summary._replace(
        probes=probe_pass(omega, A, B, block=block, precision=precision),
        probe_omega=omega)


def merge_probes(a: Optional[jax.Array],
                 b: Optional[jax.Array]) -> Optional[jax.Array]:
    """Monoid combine of two probe blocks over disjoint row sets: a plain
    sum (commutative bit-for-bit). Presence must agree on both operands."""
    if (a is None) != (b is None):
        raise ValueError("cannot merge a probe-carrying summary with a "
                         "probe-free one (build both with the same probes=)")
    return None if a is None else a + b


# ---------------------------------------------------------------------------
# A-posteriori error estimation
# ---------------------------------------------------------------------------

def _require_probes(summary: SketchSummary) -> None:
    if summary.probes is None or summary.probe_omega is None:
        raise ValueError(
            "summary carries no probe block — build it with "
            "build_summary(..., probes=p) / StreamingSummarizer(probes=p) "
            "to enable a-posteriori error estimation")


def estimate_error(summary: SketchSummary, factors: LowRankFactors, *,
                   confidence: float = 0.95) -> ErrorEstimate:
    """Unbiased a-posteriori residual estimate of ``A^T B ~= U V^T``.

    Each held-out probe ``w_j`` (a column of ``summary.probe_omega``) yields
    one unbiased sample ``||probes_j - U (V^T w_j)||^2`` of the squared
    Frobenius residual; the estimate is the sample mean, the confidence
    interval a normal approximation over the p samples, and the spectral
    proxy ``max_j ||R w_j|| / ||w_j||`` (a lower-bound estimator of
    ``||R||_2``; ``||R||_F`` bounds it from above). Pure jnp — jit/vmap
    friendly, so batched serving estimates all requests in one dispatch.

    >>> import jax, jax.numpy as jnp
    >>> from repro.core.summary_engine import build_summary
    >>> from repro.core.estimation_engine import estimate_product
    >>> key = jax.random.PRNGKey(0)
    >>> A = jax.random.normal(key, (256, 20))
    >>> B = jax.random.normal(jax.random.fold_in(key, 1), (256, 16))
    >>> s = build_summary(key, A, B, 64, probes=16)     # retain 16 probes
    >>> s.probes.shape, s.probe_omega.shape
    ((20, 16), (16, 16))
    >>> res = estimate_product(jax.random.fold_in(key, 2), s, r=4, m=600, T=3)
    >>> err = estimate_error(s, res.factors)
    >>> true = float(jnp.linalg.norm(A.T @ B - res.factors.dense()))
    >>> bool(0.5 * true < float(err.frob_est) < 2.0 * true)
    True
    >>> bool(err.frob_lo <= err.frob_est <= err.frob_hi)
    True
    """
    _require_probes(summary)
    probes, omega = summary.probes, summary.probe_omega
    p = probes.shape[-1]
    resid = probes - jnp.dot(factors.U, jnp.dot(factors.V.T, omega,
                                                precision=_HIGHEST),
                             precision=_HIGHEST)              # (n1, p)
    sq = jnp.sum(resid.astype(jnp.float32) ** 2, axis=0)      # (p,) unbiased
    frob_sq = jnp.mean(sq)
    # normal-approximation CI over the p probe samples (sample std, ddof=1;
    # a single probe carries no width information — report an honest
    # [0, inf) interval instead of a spuriously zero-width one)
    z = _Z95 if confidence == 0.95 else float(
        jax.scipy.stats.norm.ppf(0.5 + confidence / 2.0))
    if p >= 2:
        stderr = jnp.std(sq, ddof=1) / jnp.sqrt(float(p))
    else:
        stderr = jnp.asarray(jnp.inf, jnp.float32)
    frob_lo = jnp.sqrt(jnp.maximum(frob_sq - z * stderr, 0.0))
    frob_hi = jnp.sqrt(frob_sq + z * stderr)
    w_norms = jnp.sqrt(jnp.sum(omega.astype(jnp.float32) ** 2, axis=0))
    spectral = jnp.max(jnp.sqrt(sq) / jnp.maximum(w_norms, _EPS))
    # ||A^T B||_F from the same probes (unbiased, same argument)
    m_frob = jnp.sqrt(jnp.mean(
        jnp.sum(probes.astype(jnp.float32) ** 2, axis=0)))
    frob = jnp.sqrt(frob_sq)
    return ErrorEstimate(frob, frob_sq, frob_lo, frob_hi, spectral,
                         frob / jnp.maximum(m_frob, _EPS))


def rank_curve(summary: SketchSummary, r_max: int,
               refine=None) -> jax.Array:
    """Estimated relative-error curve for every rank 1..r_max (fusable stage).

    ``curve[i]`` is the estimated relative Frobenius error of the rank-(i+1)
    truncation of the rescaled sketch product, measured against the held-out
    probe block — ONE SVD and ONE probe projection for the whole curve (the
    ``adaptive_rank`` sweep, exposed as a pure traceable stage). This is what
    the PipelineEngine's quality-gated serving path reads once per bucket
    instead of re-running an estimation dispatch per candidate rank.

    ``refine`` (a ``repro.core.refinement.RefineSpec``) swaps the curve's
    factorization source from the rescaled sketch product to the
    sketch-power/Tropp refined reconstruction (needs a co-sketch-carrying
    summary) — the probe-measurement math is unchanged because the refined
    left basis is orthonormal too.
    """
    _require_probes(summary)
    rel, _, _, _ = _rank_curve(summary, r_max, refine=refine)
    return rel


# ---------------------------------------------------------------------------
# Adaptive rank selection
# ---------------------------------------------------------------------------

class AdaptiveRankResult(NamedTuple):
    """``adaptive_rank`` output: the chosen rank, its truncated factors, the
    a-posteriori estimate at that rank, and the full estimated relative-error
    curve (index i = rank i+1) the search ran over."""

    r: int
    factors: LowRankFactors
    error: ErrorEstimate
    curve: jax.Array          # (r_max,) estimated relative Frobenius errors


@functools.partial(jax.jit, static_argnames=("r_max", "refine"))
def _rank_curve(summary: SketchSummary, r_max: int, refine=None):
    """One factorization, one probe projection, every candidate rank.

    SVDs the rescaled sketch product ``M~ = D_A (A~^T B~) D_B`` once —
    or, with ``refine``, the sketch-power/Tropp refined reconstruction
    (``refinement.refined_svd``; its left basis is orthonormal, which is
    all the identity below needs) — then evaluates the estimated squared
    residual of its rank-r truncation against the probe block for ALL r in
    1..r_max via cumulative sums: with ``c = U^T probes`` and
    ``Z = diag(s) V^T Omega``,

        errsq(r)_j = ||probes_j||^2 + sum_{i<r} (Z_ij^2 - 2 c_ij Z_ij).

    Returns (rel_curve (r_max,), U, s, Vt) — O(q^2 max(n1,n2) + q p) total,
    independent of how many ranks the search probes. The whole curve is
    forced to float32 (matrix, probes, and test columns are cast before the
    reductions): a reduced-precision summary must not leak its dtype into
    the gate — on float32 inputs every cast is a bitwise no-op.
    """
    probes = summary.probes.astype(jnp.float32)
    omega = summary.probe_omega.astype(jnp.float32)
    if refine is not None:
        from repro.core.refinement import refined_svd
        U, s, Vt = refined_svd(summary, refine, r_max)
    else:
        M = estimator.rescaled_matrix(summary).astype(jnp.float32)
        U, s, Vt = jnp.linalg.svd(M, full_matrices=False)
        U, s, Vt = U[:, :r_max], s[:r_max], Vt[:r_max]
    c = jnp.dot(U.T, probes, precision=_HIGHEST)       # (r_max, p)
    Z = s[:, None] * jnp.dot(Vt, omega, precision=_HIGHEST)   # (r_max, p)
    base = jnp.sum(probes ** 2, axis=0)                # (p,)
    deltas = Z ** 2 - 2.0 * c * Z                      # (r_max, p)
    errsq = jnp.maximum(base[None, :] + jnp.cumsum(deltas, axis=0), 0.0)
    m_frob = jnp.sqrt(jnp.mean(base))
    rel = jnp.sqrt(jnp.mean(errsq, axis=1)) / jnp.maximum(m_frob, _EPS)
    return rel, U, s, Vt


def adaptive_rank(summary: SketchSummary, tol: float,
                  r_max: Optional[int] = None,
                  refine=None) -> AdaptiveRankResult:
    """Smallest rank whose *estimated* relative Frobenius error meets ``tol``.

    ``tol`` is relative: the gate is ``frob_est <= tol * ||A^T B||_F`` with
    both sides estimated from the probe block. The whole per-rank error
    curve comes from ONE factorization + ONE probe projection (cumulative
    sums), so the rank search is a scan over ``r_max`` host-side floats —
    probe noise can dent the curve's monotonicity near the noise floor, so
    an exact scan is used rather than a bisection that would silently
    return a non-minimal rank there. When no rank within ``r_max`` meets
    ``tol``, the result is ``r_max`` (callers inspect ``error.rel_est`` to
    see the gate missed). Host-level: returns a Python int rank and its
    truncated factors.

    ``refine`` (a ``repro.core.refinement.RefineSpec``) gates on the
    sketch-power/Tropp refined reconstruction instead of the raw rescaled
    sketch product (needs a co-sketch-carrying summary) — its curve sits
    below the unrefined one, so the gate passes at lower rank for the same
    ``tol``; candidate ranks are additionally capped by the co-sketch
    width s.

    >>> import jax, jax.numpy as jnp
    >>> from repro.core.summary_engine import build_summary
    >>> key = jax.random.PRNGKey(0)
    >>> W, _ = jnp.linalg.qr(jax.random.normal(key, (512, 12)))
    >>> M = (jax.random.normal(jax.random.fold_in(key, 1), (12, 10))
    ...      * jnp.array([10.0, 6.0, 0.1, 0.05, 0.02, 0.01, 0.005, 0.002,
    ...                   0.001, 0.0005])[None, :])
    >>> A, B = W, W @ M              # A^T B == M: rank ~2 + tiny tail
    >>> res = adaptive_rank(build_summary(key, A, B, 128, probes=24),
    ...                     tol=0.3, r_max=8)
    >>> (res.r, res.factors.U.shape, res.curve.shape)
    (2, (12, 2), (8,))
    >>> bool(res.error.rel_est <= 0.3)       # the chosen rank meets the gate
    True
    >>> bool(res.curve[res.r - 2] > 0.3)     # ... and is the smallest that does
    True
    """
    _require_probes(summary)
    q = min(summary.n1, summary.n2)
    if refine is not None:
        from repro.core.refinement import require_cosketch
        require_cosketch(summary)
        q = min(q, summary.n_cosketch)
    r_max = q if r_max is None else min(r_max, q)
    if r_max < 1:
        raise ValueError(f"r_max must be >= 1, got {r_max}")
    rel, U, s, Vt = _rank_curve(summary, r_max, refine=refine)
    curve = np.asarray(jax.device_get(rel))
    meets = np.flatnonzero(curve <= tol)
    r = int(meets[0]) + 1 if meets.size else int(curve.shape[0])
    factors = LowRankFactors(U[:, :r] * s[:r], Vt[:r].T)
    return AdaptiveRankResult(r, factors, estimate_error(summary, factors),
                              rel)
