"""RefinementEngine — sketch-power iterations + Tropp-style reconstruction.

The paper's single-pass guarantee is fixed by the retained sketch; two
PAPERS.md upgrades buy more accuracy per retained byte *without extra data
passes*:

* **Tropp et al. 1609.00048** (practical sketching): retain a second
  *co-sketch* block alongside the JL sketches — the range/co-range pair

      Y = (A^T B) @ Omega_c          (n1, s)   range sketch
      W = Psi_c @ (A^T B)            (l, n2)   co-range sketch, l = 2s + 1

  with ``Omega_c`` (n2, s) and ``Psi_c`` (l, n1) Gaussian test matrices
  derived from the summary key (``l = 2s + 1`` is Tropp's recommended
  co-range oversampling — it keeps the reconstruction least-squares
  overdetermined). Both blocks are **linear in the rows** of
  (A, B) — per row ``a_t (b_t^T Omega_c)`` and ``(Psi_c a_t) b_t^T`` — so
  they accumulate in the same single pass, ride the streaming monoid as
  plain sums, and psum across shards exactly like the sketches and probes.
  The stabilized reconstruction is Tropp's Algorithm 7:
  ``Q = qr(Y)``, ``X = (Psi_c Q)^+ W``, ``A^T B ~= Q X`` — the co-range
  block *corrects* the range estimate, so the factorization error tracks
  the true tail of A^T B instead of the sketch noise floor.

* **Chang & Yang** (sketch-power iterations): power-iteration accuracy
  without revisiting the data — subspace-iterate the retained range basis
  against the *rescaled sketch product* ``M~ = D_A (A~^T B~) D_B`` (the
  paper's estimator, already in the summary), warm-started from the exact
  ``Y``, then apply the same Tropp reconstruction from the refined basis.

``RefineSpec`` is the declarative knob: ``method='tropp'`` is the pure
(Y, W) reconstruction, ``method='power'`` prepends ``iters`` sketch-power
iterations. It is a hashable NamedTuple, so it joins ``PipelinePlan`` (and
therefore every executable cache key) and the jitted estimator cells'
static arguments — warm serving under a pinned refinement never re-traces.

Randomness contract: the test matrices are pure functions of the summary
key through the reserved two-level fold ``fold_in(fold_in(key, 0x63736B21),
0 | 1)`` ("csk!"; sub-index 0 = Omega_c, 1 = Psi_c) — the same scheme as
the probe ("prob"/"e!"), window ("wdw!") and tenant ("tnt!") folds, so the
co-sketch randomness can never collide with any per-row single fold and is
identical across backends, chunkings, and merge orders (golden-pinned in
tests/core/test_key_contract.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import estimator
from repro.core.types import LowRankFactors, SketchSummary

# "csk!" — the reserved fold tag for the co-sketch key subtree
_COSKETCH_TAG = 0x63736B21

#: sub-indices under the tag fold: Omega_c (range test) / Psi_c (co-range)
_OMEGA_SUB = 0
_PSI_SUB = 1

REFINE_METHODS = ("tropp", "power")


class RefineSpec(NamedTuple):
    """Declarative refinement stage: how to rebuild factors from the
    retained co-sketch block.

    ``method='tropp'`` — the stabilized (Y, W) reconstruction alone
    (``iters`` is ignored); ``method='power'`` — ``iters`` sketch-power
    subspace iterations against the rescaled sketch product first, then
    the same reconstruction from the refined basis. Hashable: joins
    ``PipelinePlan`` and the jitted estimator cells' static arguments.
    """

    iters: int = 0
    method: str = "tropp"


def validate_refine(refine: "RefineSpec") -> None:
    """Reject a malformed RefineSpec eagerly (before any trace)."""
    if not isinstance(refine, RefineSpec):
        raise TypeError(
            f"expected a RefineSpec, got {type(refine).__name__}")
    if refine.method not in REFINE_METHODS:
        raise ValueError(f"unknown refinement method {refine.method!r} "
                         f"(use one of {REFINE_METHODS})")
    if isinstance(refine.iters, bool) or not isinstance(refine.iters, int) \
            or refine.iters < 0:
        raise ValueError(
            f"RefineSpec.iters must be a non-negative int, "
            f"got {refine.iters!r}")


# ---------------------------------------------------------------------------
# The co-sketch block (single-pass accumulation primitives)
# ---------------------------------------------------------------------------

def cosketch_key(key: jax.Array) -> jax.Array:
    """The reserved co-sketch subtree of the summary key (the tag fold)."""
    return jax.random.fold_in(key, _COSKETCH_TAG)


def cosketch_omega(key: jax.Array, n2: int, s: int) -> jax.Array:
    """(n2, s) Gaussian range test matrix Omega_c — a pure function of the
    summary key, identical on every backend/chunking/merge order."""
    return jax.random.normal(
        jax.random.fold_in(cosketch_key(key), _OMEGA_SUB), (n2, s))


def cosketch_width(s: int) -> int:
    """Co-range rows l for a width-s range sketch: Tropp's l = 2s + 1.

    The stabilized reconstruction solves ``min_X ||(Psi_c Q) X - W||`` with
    ``Psi_c Q`` of shape (l, q <= s); l > s keeps that least-squares problem
    overdetermined and well-conditioned (a square system degenerates to an
    oblique projection whose error blows up with cond(Psi_c Q))."""
    return 2 * s + 1


def cosketch_psi(key: jax.Array, n1: int, s: int) -> jax.Array:
    """(l, n1) Gaussian co-range test matrix Psi_c with ``l =
    cosketch_width(s)`` — same key contract as ``cosketch_omega`` under the
    sibling sub-fold."""
    return jax.random.normal(
        jax.random.fold_in(cosketch_key(key), _PSI_SUB),
        (cosketch_width(s), n1))


def cosketch_contribution(omega: jax.Array, psi: jax.Array,
                          A_chunk: jax.Array, B_chunk: jax.Array,
                          precision: Optional[str] = None
                          ) -> Tuple[jax.Array, jax.Array]:
    """One row chunk's (dY, dW) co-sketch summands.

    ``dY = A_chunk^T (B_chunk @ Omega_c)`` (n1, s) and
    ``dW = (Psi_c @ A_chunk^T) B_chunk`` (l, n2), both with f32
    accumulation regardless of input dtype (f32 operands stay f32 on a TPU,
    as in ``summary_engine._sketch_dot``) — the exact float ops the
    streaming update and the one-shot ``cosketch_pass`` share (the
    bit-parity contract). A zero-row chunk contributes exact zeros (the
    monoid identity).
    """
    from repro.core.summary_engine import _cast
    Ac, Bc = _cast(A_chunk, precision), _cast(B_chunk, precision)
    Bw = jax.lax.dot_general(Bc, _cast(omega, precision).astype(Bc.dtype),
                             dimension_numbers=(((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    dY = jax.lax.dot_general(Ac, Bw.astype(Ac.dtype),
                             dimension_numbers=(((0,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    pA = jax.lax.dot_general(_cast(psi, precision).astype(Ac.dtype), Ac,
                             dimension_numbers=(((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    dW = jax.lax.dot_general(pA.astype(Bc.dtype), Bc,
                             dimension_numbers=(((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    return dY, dW


@functools.partial(jax.jit, static_argnames=("block", "precision"))
def cosketch_pass(omega: jax.Array, psi: jax.Array, A: jax.Array,
                  B: jax.Array, *, block: int = 1024,
                  precision: Optional[str] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """(Y, W) over the whole in-memory pair: a ``lax.scan`` over row blocks
    mirroring the scan backend's block structure (zero-padded trailing
    block), so sequential streamed ingestion at chunk ``block`` is
    bit-identical to this one-shot pass."""
    d, n1 = A.shape
    n2 = B.shape[1]
    s, l = omega.shape[1], psi.shape[0]
    pad = (-d) % block
    Ablk = jnp.pad(A, ((0, pad), (0, 0))).reshape(-1, block, n1)
    Bblk = jnp.pad(B, ((0, pad), (0, 0))).reshape(-1, block, n2)

    def _body(acc, ab):
        Ab, Bb = ab
        dY, dW = cosketch_contribution(omega, psi, Ab, Bb, precision)
        return (acc[0] + dY, acc[1] + dW), None

    init = (jnp.zeros((n1, s), jnp.float32), jnp.zeros((l, n2), jnp.float32))
    (Y, W), _ = jax.lax.scan(_body, init, (Ablk, Bblk))
    return Y, W


def attach_cosketch(summary: SketchSummary, key: jax.Array, A: jax.Array,
                    B: jax.Array, s: int, *, block: int = 1024,
                    precision: Optional[str] = None) -> SketchSummary:
    """Retain an s-column co-sketch block on an existing summary (the
    backend-independent stage ``build_summary(..., cosketch=s)`` runs after
    dispatch, exactly like the probe attach).

    >>> import jax
    >>> key = jax.random.PRNGKey(0)
    >>> A = jax.random.normal(key, (64, 6))
    >>> B = jax.random.normal(jax.random.fold_in(key, 1), (64, 4))
    >>> from repro.core.summary_engine import build_summary
    >>> s = build_summary(key, A, B, 8, cosketch=3)
    >>> (s.cosketch_Y.shape, s.cosketch_W.shape)    # W rows: l = 2s + 1
    ((6, 3), (7, 4))
    >>> (s.cosketch_omega.shape, s.cosketch_psi.shape)
    ((4, 3), (7, 6))
    """
    omega = cosketch_omega(key, B.shape[-1], s)
    psi = cosketch_psi(key, A.shape[-1], s)
    Y, W = cosketch_pass(omega, psi, A, B, block=block, precision=precision)
    return summary._replace(cosketch_Y=Y, cosketch_W=W,
                            cosketch_omega=omega, cosketch_psi=psi)


def merge_cosketch(a: Optional[jax.Array],
                   b: Optional[jax.Array]) -> Optional[jax.Array]:
    """Monoid combine of two co-sketch blocks (Y with Y, W with W) over
    disjoint row sets: a plain sum (commutative bit-for-bit). Presence
    must agree on both operands."""
    if (a is None) != (b is None):
        raise ValueError(
            "cannot merge a cosketch-carrying summary with a cosketch-free "
            "one (build both with the same cosketch=)")
    return None if a is None else a + b


def require_cosketch(summary: SketchSummary) -> None:
    """Reject summaries without the retained (Y, W) pair."""
    if summary.cosketch_Y is None or summary.cosketch_W is None or \
            summary.cosketch_psi is None:
        raise ValueError(
            "summary carries no co-sketch block — build it with "
            "build_summary(..., cosketch=s) / StreamingSummarizer(cosketch="
            "s) to enable sketch-power/Tropp refinement "
            "(estimate_product(method='power') / rank_curve(refine=...))")


# ---------------------------------------------------------------------------
# Refined factorization
# ---------------------------------------------------------------------------

def refined_svd(summary: SketchSummary, refine: RefineSpec, r_max: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(U, s, Vt) of the Tropp-stabilized reconstruction, truncated to
    ``r_max`` — the refined drop-in for ``svd(rescaled_matrix(summary))``.

    ``method='tropp'``: ``Q = qr(Y)``, ``X = (Psi_c Q)^+ W`` (least
    squares), SVD(X) rotated back through Q. ``method='power'``: the basis
    is first subspace-iterated ``iters`` times against the rescaled sketch
    product ``M~`` (QR re-orthonormalization each step; no data pass —
    everything lives in the retained summary), then reconstructed the same
    way. All in float32: the curve/gate downstream must not inherit a
    low-precision summary dtype. Pure jnp — jit/vmap friendly.
    """
    Y = summary.cosketch_Y.astype(jnp.float32)
    W = summary.cosketch_W.astype(jnp.float32)
    psi = summary.cosketch_psi.astype(jnp.float32)
    Q, _ = jnp.linalg.qr(Y)
    if refine.method == "power" and refine.iters > 0:
        M = estimator.rescaled_matrix(summary).astype(jnp.float32)
        for _ in range(refine.iters):          # iters is static (RefineSpec)
            Q, _ = jnp.linalg.qr(M @ (M.T @ Q))
    X = jnp.linalg.lstsq(psi @ Q, W)[0]        # (q, n2) stabilized co-range
    Ub, sv, Vt = jnp.linalg.svd(X, full_matrices=False)
    U = Q @ Ub
    return U[:, :r_max], sv[:r_max], Vt[:r_max]


def refine_factors(summary: SketchSummary, r: int,
                   refine: RefineSpec) -> LowRankFactors:
    """Rank-r factors of A^T B from the refined reconstruction.

    >>> import jax, jax.numpy as jnp
    >>> from repro.core.summary_engine import build_summary
    >>> key = jax.random.PRNGKey(0)
    >>> W0, _ = jnp.linalg.qr(jax.random.normal(key, (256, 10)))
    >>> M = jax.random.normal(jax.random.fold_in(key, 1), (10, 8))
    >>> A, B = W0, W0 @ M                       # A^T B == M exactly
    >>> s = build_summary(key, A, B, 32, cosketch=8)
    >>> f = refine_factors(s, 3, RefineSpec(iters=1, method='power'))
    >>> (f.U.shape, f.V.shape)
    ((10, 3), (8, 3))
    """
    require_cosketch(summary)
    U, sv, Vt = refined_svd(summary, refine, r)
    return LowRankFactors(U * sv, Vt.T)
