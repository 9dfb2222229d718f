"""Core pytree types for SMP-PCA.

Everything is a NamedTuple so it is a natural JAX pytree, jit/pjit friendly,
and serializable by the checkpoint layer.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class SketchSummary(NamedTuple):
    """One-pass summary of (A, B) per Algorithm 1 step 1.

    A: (d, n1), B: (d, n2); sketches are (k, n1)/(k, n2). Column norms are the
    paper's *side information* that powers the rescaled JL estimator.

    ``probes``/``probe_omega`` are the optional held-out probe block (the
    ErrorEngine's a-posteriori quality side information, Tropp et al.
    1609.00048): ``probes = (A^T B) @ probe_omega`` accumulated in the same
    single pass, ``probe_omega`` the (n2, p) Gaussian test matrix derived
    from the sketch key. Both are None when the summary was built without
    probes (``build_summary(..., probes=0)``, the default).

    ``cosketch_*`` is the optional Tropp range/co-range pair retained for
    sketch-power/Tropp refinement (RefinementEngine): ``cosketch_Y =
    (A^T B) @ cosketch_omega`` (n1, s) and ``cosketch_W = cosketch_psi @
    (A^T B)`` (l, n2) with ``l = 2s + 1`` (Tropp's co-range oversampling),
    accumulated in the same single pass, with the
    (n2, s)/(l, n1) Gaussian test matrices derived from the sketch key
    under the reserved "csk!" fold. All four stay None by default
    (``build_summary(..., cosketch=0)``) so legacy treedefs, checkpoints,
    and the streaming monoid are unchanged when refinement is off.
    """

    A_sketch: jax.Array        # (k, n1) = Pi @ A
    B_sketch: jax.Array        # (k, n2) = Pi @ B
    norm_A: jax.Array          # (n1,)  exact column L2 norms of A
    norm_B: jax.Array          # (n2,)  exact column L2 norms of B
    probes: Optional[jax.Array] = None       # (n1, p) = A^T (B @ probe_omega)
    probe_omega: Optional[jax.Array] = None  # (n2, p) held-out Gaussian probes
    cosketch_Y: Optional[jax.Array] = None      # (n1, s) range co-sketch
    cosketch_W: Optional[jax.Array] = None      # (l, n2) co-range co-sketch
    cosketch_omega: Optional[jax.Array] = None  # (n2, s) range test matrix
    cosketch_psi: Optional[jax.Array] = None    # (l, n1) co-range test matrix

    @property
    def k(self) -> int:
        """Sketch size (rows of the sketches)."""
        return self.A_sketch.shape[0]

    @property
    def n1(self) -> int:
        """Columns of A."""
        return self.A_sketch.shape[1]

    @property
    def n2(self) -> int:
        """Columns of B."""
        return self.B_sketch.shape[1]

    @property
    def frob_A(self) -> jax.Array:
        """Frobenius norm of A (from the retained column norms)."""
        return jnp.sqrt(jnp.sum(self.norm_A ** 2))

    @property
    def frob_B(self) -> jax.Array:
        """Frobenius norm of B (from the retained column norms)."""
        return jnp.sqrt(jnp.sum(self.norm_B ** 2))

    @property
    def n_probes(self) -> int:
        """Held-out probe count p (0 when no probe block was retained)."""
        return 0 if self.probes is None else self.probes.shape[-1]

    @property
    def n_cosketch(self) -> int:
        """Co-sketch width s (0 when no refinement block was retained)."""
        return 0 if self.cosketch_Y is None else self.cosketch_Y.shape[-1]


class SampleSet(NamedTuple):
    """A static-shape COO sample of entries of the (n1 x n2) product matrix.

    ``rows/cols`` index into A's / B's columns. ``q_hat`` is min(1, q_ij) used
    for the 1/q_hat completion weights. ``mask`` marks valid entries (padding
    allows static shapes under jit).
    """

    rows: jax.Array            # (m,) int32
    cols: jax.Array            # (m,) int32
    q_hat: jax.Array           # (m,) float32
    mask: jax.Array            # (m,) bool

    @property
    def m(self) -> int:
        """Static sample budget (padded length)."""
        return self.rows.shape[0]


class LowRankFactors(NamedTuple):
    """Rank-r approximation in factored form: M_hat = U @ V^T."""

    U: jax.Array               # (n1, r)
    V: jax.Array               # (n2, r)

    @property
    def r(self) -> int:
        """Factor rank."""
        return self.U.shape[1]

    def dense(self) -> jax.Array:
        """Materialize the (n1, n2) approximation U @ V^T."""
        return self.U @ self.V.T


class ErrorEstimate(NamedTuple):
    """A-posteriori quality estimate of rank-r factors (ErrorEngine output).

    All statistics come from the p held-out probe columns retained in the
    summary: each probe gives one unbiased sample of the squared Frobenius
    residual ``||A^T B - U V^T||_F^2``, and the fields below are the sample
    mean, a normal-approximation confidence interval over the p samples, a
    spectral-norm proxy, and the residual relative to the estimated
    ``||A^T B||_F``. Every field is a scalar array, so the estimate vmaps
    across batched (L, ...) results.
    """

    frob_est: jax.Array       # sqrt of the unbiased mean squared residual
    frob_sq_est: jax.Array    # unbiased estimate of ||A^T B - U V^T||_F^2
    frob_lo: jax.Array        # lower confidence bound on the Frobenius residual
    frob_hi: jax.Array        # upper confidence bound on the Frobenius residual
    spectral_est: jax.Array   # max_j ||R w_j|| / ||w_j|| — spectral-norm proxy
    rel_est: jax.Array        # frob_est / estimated ||A^T B||_F


class EstimateResult(NamedTuple):
    """Step-2/3 output of the EstimationEngine (``estimate_product``).

    ``samples``/``values`` carry the Omega sample and the estimated entries
    for the completion methods; both are None for ``method='direct_svd'``
    (which never samples). ``error`` is the ErrorEngine's a-posteriori
    quality estimate, filled only by ``estimate_product(..., with_error=
    True)`` on probe-carrying summaries. None fields are empty pytree nodes,
    so the result stays jit/vmap friendly across methods.
    """

    factors: LowRankFactors
    samples: Optional[SampleSet]
    values: Optional[jax.Array]   # (m,) estimated entries on Omega
    error: Optional[ErrorEstimate] = None


class SMPPCAResult(NamedTuple):
    """Full Algorithm-1 output: factors plus the intermediates."""

    factors: LowRankFactors
    summary: SketchSummary
    samples: SampleSet
    sampled_values: jax.Array  # (m,) rescaled-JL estimates on Omega


@jax.tree_util.register_pytree_node_class
class SparseRows:
    """A chunk of ``t`` rows of a ``(d, n)`` matrix held as its nonzeros.

    ``rows`` (chunk-local row, in ``[0, t)``), ``cols`` and ``vals`` are
    ``(capacity,)`` arrays; an entry with ``vals == 0`` is padding and adds
    nothing. The capacity is fixed by the caller, so one compiled update
    serves every chunk of that capacity. ``shape = (t, n)`` is static (the
    pytree's aux data), so ``shape[0]`` counts rows as for a dense chunk.
    A (row, column) pair may appear more than once: its values add.

    >>> import jax.numpy as jnp
    >>> X = jnp.array([[0., 2., 0.], [1., 0., 0.]])
    >>> S = SparseRows.from_dense(X, capacity=4)
    >>> S.shape, S.capacity, bool(jnp.all(S.todense() == X))
    ((2, 3), 4, True)
    """

    def __init__(self, rows: jax.Array, cols: jax.Array, vals: jax.Array,
                 shape):
        self.rows, self.cols, self.vals = rows, cols, vals
        self.shape = (int(shape[0]), int(shape[1]))

    def tree_flatten(self):
        return (self.rows, self.cols, self.vals), self.shape

    @classmethod
    def tree_unflatten(cls, shape, leaves):
        return cls(*leaves, shape)

    @property
    def capacity(self) -> int:
        """Entries held, padding included."""
        return self.vals.shape[0]

    def todense(self) -> jax.Array:
        """The ``(t, n)`` float32 chunk."""
        return jnp.zeros(self.shape, jnp.float32).at[self.rows, self.cols].add(
            self.vals.astype(jnp.float32))

    @classmethod
    def from_dense(cls, X: jax.Array, capacity: int) -> "SparseRows":
        """The nonzeros of a dense ``(t, n)`` chunk, padded to ``capacity``
        (which must hold them all: entries past it would be lost)."""
        nnz = jnp.count_nonzero(X)
        if not isinstance(nnz, jax.core.Tracer) and int(nnz) > capacity:
            raise ValueError(f"{int(nnz)} nonzeros do not fit capacity "
                             f"{capacity}")
        rows, cols = jnp.nonzero(X, size=capacity, fill_value=0)
        vals = jnp.where(jnp.arange(capacity) < nnz, X[rows, cols],
                         0).astype(jnp.float32)
        return cls(rows.astype(jnp.int32), cols.astype(jnp.int32), vals,
                   X.shape)
