"""SummaryEngine — ONE entry point for the paper's Step-1 single pass.

``build_summary(key, A, B, k, method=..., backend=...)`` produces the
``SketchSummary`` (sketches + exact column norms) that every downstream
stage (sampling, rescaled-JL, WAltMin, gradient compression, serving)
consumes. The five historical implementations are registered here as
*backends* behind one shared randomness contract, following the
one-abstraction/many-instantiations design of Tropp et al.'s practical
sketching framework:

    reference    materialized projection operator, one dense matmul
                 (the semantic oracle every other backend is tested against)
    scan         block-streamed ``lax.scan`` over row blocks; the projection
                 slice for each block is regenerated on the fly so the full
                 (k, d) operator never exists (the paper's streaming pass)
    rows         arbitrary-order row streaming (``rows_summary``): rows may
                 arrive as (global index, A row, B row) triples in any order
    pallas       fused TPU kernel(s): one HBM pass produces the sketch on the
                 MXU and the column norms on the VPU (kernels/sketch_fused);
                 SRHT uses the blocked-FWHT MXU kernel (kernels/hadamard)
    distributed  row-sharded ``shard_map`` + psum — Spark treeAggregate as a
                 single ICI all-reduce (core/distributed)

Shared randomness contract (what makes the backends interchangeable):

* ``method='gaussian'``: the projection column for global row ``i`` is
  ``normal(fold_in(key, i), (k,)) / sqrt(k)`` — a pure function of
  ``(key, i)``, so any partition of the rows (blocks, shards, arbitrary
  streams) accumulates to the same summary.
* ``method='srht'``: signs and sampled Hadamard rows are derived once from
  ``key`` (``srht_plan``); the projection column for row ``i`` is
  ``signs[i] * H[rows, i] / sqrt(k)`` where ``H[r, i] = (-1)^popcount(r & i)``
  is the Sylvester Hadamard entry — computable pointwise, which is what lets
  SRHT stream row-by-row even though H globally mixes all rows.

Batched mode: pass ``A``/``B`` with a leading stack axis ``(L, d, n)`` and the
engine sketches all L pairs in one vmapped dispatch (one key per pair, either
``split(key, L)`` or an explicit key stack) — the per-layer case the gradient
compressor needs.

Precision: ``precision='bf16'`` casts inputs to bfloat16 while every
accumulation (MXU contraction and norm reduction) stays float32
(bf16-in/f32-accumulate); sketches and norms are always float32 outputs.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.sketch import (
    _next_pow2, column_norms, gaussian_pi, pi_rows)
from repro.core.types import SketchSummary

METHODS = ("gaussian", "srht")

_BACKENDS: Dict[str, Callable] = {}


def register_backend(name: str):
    """Register ``fn(key, A, B, k, *, method, block, precision, tuning,
    **kw)``. ``tuning`` is an optional hashable
    ``repro.kernels.tuning.TuningSpec``; only kernel-backed backends act on
    it (the others must accept and ignore it so one plan drives any
    backend)."""
    def _deco(fn):
        _BACKENDS[name] = fn
        return fn
    return _deco


def backends() -> tuple:
    """All registered summary backend names."""
    return tuple(sorted(_BACKENDS))


# ---------------------------------------------------------------------------
# Shared randomness + precision plumbing
# ---------------------------------------------------------------------------

def _cast(x: jax.Array, precision: Optional[str]) -> jax.Array:
    """precision=None keeps the input dtype (bf16 data stays bf16-in; no
    upcast copy is materialized) — accumulation is f32 regardless via
    ``preferred_element_type`` and the f32 norm reductions."""
    if precision is None:
        return x
    if precision == "f32":
        return x if x.dtype == jnp.float32 else x.astype(jnp.float32)
    if precision == "bf16":
        return x.astype(jnp.bfloat16)
    raise ValueError(f"unknown precision {precision!r} (use None|'f32'|'bf16')")


def srht_plan(key: jax.Array, d: int, k: int):
    """(signs (d,), sampled Hadamard rows (k,), dp): the SRHT randomness.

    The derivation (key split, rademacher signs, no-replacement row sample
    over the power-of-two padded dimension) matches ``core.sketch.srht_sketch``
    and ``kernels.ops.srht_sketch_kernel`` so all backends share one plan."""
    dp = _next_pow2(d)
    if k > dp:
        raise ValueError(
            f"srht needs k <= next_pow2(d): k={k} exceeds the padded "
            f"dimension dp={dp} (d={d}) — no-replacement row sampling "
            f"cannot draw k rows from dp")
    key_sign, key_rows = jax.random.split(key)
    signs = jax.random.rademacher(key_sign, (d,), dtype=jnp.float32)
    rows = jax.random.choice(key_rows, dp, (k,), replace=False)
    return signs, rows, dp


def hadamard_cols(sampled_rows: jax.Array, row_idx: jax.Array) -> jax.Array:
    """H[sampled_rows][:, row_idx] for the Sylvester Hadamard matrix, via
    ``H[r, i] = (-1)^popcount(r & i)`` — O(k * t) pointwise, no transform."""
    r = sampled_rows.astype(jnp.int32)[:, None]
    i = row_idx.astype(jnp.int32)[None, :]
    bit = jax.lax.population_count(r & i) & 1
    return (1 - 2 * bit).astype(jnp.float32)


def srht_rows_from_plan(signs_rows: jax.Array, sampled_rows: jax.Array,
                        row_idx: jax.Array, k: int) -> jax.Array:
    """(t, k) SRHT projection columns for global rows ``row_idx`` given the
    plan: ``signs_rows`` are the sign entries already gathered/sliced for
    ``row_idx``. THE one place the streamed-SRHT column formula lives — the
    reference, scan, rows, and distributed backends all call this, which is
    what the cross-backend parity contract rests on."""
    Hc = hadamard_cols(sampled_rows, row_idx)                   # (k, t)
    return (Hc * signs_rows[None, :]).T / jnp.sqrt(k)


def projection_rows(key: jax.Array, row_idx: jax.Array, k: int, *,
                    method: str = "gaussian", d_total: Optional[int] = None,
                    plan=None) -> jax.Array:
    """Columns of the (k, d) sketch operator for the given global row ids.

    Returns (t, k) with ``[t, :] = Pi[:, row_idx[t]]`` — the engine's
    randomness contract in one function. For srht, pass either ``d_total``
    (the global streamed dimension; the plan is derived from ``key``) or a
    precomputed ``plan = srht_plan(key, d_total, k)[:2]`` — streaming
    callers should derive the plan once and reuse it per chunk rather than
    paying the O(d_total) derivation every time."""
    if method == "gaussian":
        return pi_rows(key, row_idx, k)
    if method == "srht":
        if plan is not None:
            signs, rows = plan[0], plan[1]
        elif d_total is not None:
            signs, rows, _ = srht_plan(key, d_total, k)
        else:
            raise ValueError("method='srht' needs d_total or plan=")
        s = signs[jnp.clip(row_idx, 0, signs.shape[0] - 1)]     # pad rows -> 0 data
        return srht_rows_from_plan(s, rows, row_idx, k)
    raise ValueError(f"unknown sketch method {method!r} (use {METHODS})")


def _sketch_dot(P: jax.Array, X: jax.Array,
                precision: Optional[str]) -> jax.Array:
    """(t, k)^T @ (t, n) with f32 accumulation regardless of input dtype.

    The freshly generated projection is cast to X's (possibly reduced)
    dtype — never the data up — so low-precision inputs hit the MXU at
    full rate with f32 accumulation. ``Precision.HIGHEST`` keeps f32
    operands f32 on a TPU, whose default rounds them to bf16; it changes
    nothing for bf16 operands or on the CPU."""
    Xc = _cast(X, precision)
    return jax.lax.dot_general(
        _cast(P, precision).astype(Xc.dtype), Xc,
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------

@register_backend("reference")
@functools.partial(jax.jit, static_argnames=("k", "method", "block",
                                             "precision", "tuning"))
def _reference_backend(key, A, B, k: int, *, method: str = "gaussian",
                       block: int = 1024, precision: Optional[str] = None,
                       tuning=None) -> SketchSummary:
    """Materialized projection operator + one dense contraction per matrix."""
    del block, tuning
    d = A.shape[0]
    P = projection_rows(key, jnp.arange(d), k, method=method, d_total=d)
    Ac, Bc = _cast(A, precision), _cast(B, precision)
    return SketchSummary(
        _sketch_dot(P, Ac, precision), _sketch_dot(P, Bc, precision),
        column_norms(Ac), column_norms(Bc))


@register_backend("rows")
def _rows_backend(key, A, B, k: int, *, method: str = "gaussian",
                  block: int = 1024, precision: Optional[str] = None,
                  tuning=None) -> SketchSummary:
    """Row-stream semantics over the full in-memory pair (rows 0..d-1)."""
    del block, tuning
    d = A.shape[0]
    return rows_summary(key, jnp.arange(d), A, B, k, method=method,
                        d_total=d, precision=precision)


@functools.partial(jax.jit, static_argnames=("k", "method", "d_total",
                                             "precision"))
def rows_summary(key: jax.Array, row_idx: jax.Array, A_rows: jax.Array,
                 B_rows: jax.Array, k: int, *, method: str = "gaussian",
                 d_total: Optional[int] = None, plan=None,
                 precision: Optional[str] = None) -> SketchSummary:
    """Arbitrary-order streaming: rows arrive as (index, A row, B row)
    triples; the result is independent of arrival order (a sum over rows).
    Partial streams combine with ``core.sketch.merge_summaries``. For
    ``method='srht'`` pass ``d_total`` (the global streamed dimension) — or,
    when summarizing many chunks, derive ``plan = srht_plan(key, d, k)[:2]``
    once and pass it per chunk to skip the repeated O(d) plan derivation."""
    P = projection_rows(key, row_idx, k, method=method, d_total=d_total,
                        plan=plan)
    Ac, Bc = _cast(A_rows, precision), _cast(B_rows, precision)
    return SketchSummary(
        _sketch_dot(P, Ac, precision), _sketch_dot(P, Bc, precision),
        column_norms(Ac), column_norms(Bc))


@register_backend("scan")
@functools.partial(jax.jit, static_argnames=("k", "method", "block",
                                             "precision", "tuning"))
def _scan_backend(key, A, B, k: int, *, method: str = "gaussian",
                  block: int = 1024, precision: Optional[str] = None,
                  tuning=None) -> SketchSummary:
    """Single ``lax.scan`` pass over row blocks; each block regenerates its
    projection slice from (key, global row ids) so the (k, d) operator never
    exists — the memory model of the paper's streaming pass and of the fused
    TPU kernel."""
    del tuning
    d, n1 = A.shape
    n2 = B.shape[1]
    pad = (-d) % block
    Ablk = jnp.pad(A, ((0, pad), (0, 0))).reshape(-1, block, n1)
    Bblk = jnp.pad(B, ((0, pad), (0, 0))).reshape(-1, block, n2)
    nblk = Ablk.shape[0]

    if method == "srht":
        signs, srows, _ = srht_plan(key, d, k)
        # pad-row signs are irrelevant (their data rows are zero)
        signs_blk = jnp.pad(signs, (0, pad), constant_values=1.0
                            ).reshape(nblk, block)
    else:
        signs_blk = jnp.ones((nblk, block), jnp.float32)
        srows = None

    def _body(carry, inputs):
        As, Bs, na2, nb2 = carry
        bi, Ab, Bb, sb = inputs
        gids = bi * block + jnp.arange(block)
        if method == "gaussian":
            P_b = pi_rows(key, gids, k)                         # (block, k)
        else:
            P_b = srht_rows_from_plan(sb, srows, gids, k)
        Ac, Bc = _cast(Ab, precision), _cast(Bb, precision)
        As = As + _sketch_dot(P_b, Ac, precision)
        Bs = Bs + _sketch_dot(P_b, Bc, precision)
        na2 = na2 + jnp.sum(Ac.astype(jnp.float32) ** 2, axis=0)
        nb2 = nb2 + jnp.sum(Bc.astype(jnp.float32) ** 2, axis=0)
        return (As, Bs, na2, nb2), None

    init = (jnp.zeros((k, n1), jnp.float32), jnp.zeros((k, n2), jnp.float32),
            jnp.zeros((n1,), jnp.float32), jnp.zeros((n2,), jnp.float32))
    (As, Bs, na2, nb2), _ = jax.lax.scan(
        _body, init, (jnp.arange(nblk), Ablk, Bblk, signs_blk))
    return SketchSummary(As, Bs, jnp.sqrt(na2), jnp.sqrt(nb2))


@register_backend("pallas")
def _pallas_backend(key, A, B, k: int, *, method: str = "gaussian",
                    block: int = 1024, precision: Optional[str] = None,
                    tuning=None) -> SketchSummary:
    """Kernel-backed pass: the fused sketch+norms kernel for gaussian, the
    blocked-FWHT MXU kernel (sign flip fused into its first stage) for srht.
    ``interpret`` is auto-detected from the platform inside kernels/ops.
    ``tuning`` (a ``TuningSpec``) pins kernel block configs; absent ones
    resolve via the committed tuning table / frozen defaults inside ops."""
    from repro.kernels import ops as kops
    del block
    cfg_sketch = tuning.config_for("sketch_fused") if tuning else None
    cfg_fwht = tuning.config_for("blocked_fwht") if tuning else None
    d = A.shape[0]
    if method == "gaussian":
        P = projection_rows(key, jnp.arange(d), k).T             # (k, d)
        As, na = kops.sketch_fused(P, A, precision=precision, config=cfg_sketch)
        Bs, nb = kops.sketch_fused(P, B, precision=precision, config=cfg_sketch)
        return SketchSummary(As, Bs, na, nb)
    if method == "srht":
        signs, rows, dp = srht_plan(key, d, k)
        signs_p = jnp.pad(signs, (0, dp - d), constant_values=1.0)

        def _one(X):
            # the FWHT kernel casts tiles to f32 in its body; feed the
            # (possibly reduced-precision) input straight in
            Xp = jnp.pad(_cast(X, precision), ((0, dp - d), (0, 0)))
            HX = kops.blocked_fwht(Xp, signs_p, config=cfg_fwht) / jnp.sqrt(dp)
            return HX[rows] * jnp.sqrt(dp / k)

        Ac, Bc = _cast(A, precision), _cast(B, precision)
        return SketchSummary(_one(A), _one(B), column_norms(Ac),
                             column_norms(Bc))
    raise ValueError(f"unknown sketch method {method!r} (use {METHODS})")


@register_backend("distributed")
def _distributed_backend(key, A, B, k: int, *, method: str = "gaussian",
                         block: int = 1024, precision: Optional[str] = None,
                         tuning=None, mesh=None, axis: Optional[str] = None
                         ) -> SketchSummary:
    """Row-sharded shard_map pass; requires ``mesh`` and ``axis`` kwargs."""
    del block, tuning
    if mesh is None or axis is None:
        raise ValueError("backend='distributed' needs mesh=... and axis=...")
    from repro.core.distributed import distributed_sketch_summary
    return distributed_sketch_summary(mesh, axis, key, A, B, k,
                                      method=method, precision=precision)


# ---------------------------------------------------------------------------
# The entry point
# ---------------------------------------------------------------------------

def _is_key_stack(key, L: int) -> bool:
    """True if ``key`` is a stack of L per-pair keys (raw (L, 2) uint32 or a
    (L,) typed-key array) rather than one key to split L ways."""
    ndim = jnp.ndim(key)
    if ndim == 2:
        return key.shape[0] == L
    if ndim == 1 and jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key):
        return key.shape[0] == L
    return False


def build_summary(key: jax.Array, A: jax.Array, B: jax.Array, k: int, *,
                  method: str = "gaussian", backend: str = "reference",
                  block: int = 1024, precision: Optional[str] = None,
                  probes: int = 0, cosketch: int = 0, tuning=None, mesh=None,
                  axis: Optional[str] = None) -> SketchSummary:
    """One-pass summary of (A, B): sketches (k, n) + exact column norms.

    A: (d, n1), B: (d, n2) — or stacked (L, d, n1)/(L, d, n2) for the batched
    mode, which vmaps the chosen backend over the L pairs in one dispatch
    (``key`` is split per pair, or pass a stack of L keys).

    method:  'gaussian' (the paper's analyzed JL sketch) | 'srht'
    backend: one of ``backends()`` — identical (key, global row id) randomness
             across backends, so outputs agree to float reassociation.
    block:   row-block size for the scan backend.
    precision: None/'f32' | 'bf16' (bf16 inputs, f32 accumulation).
    probes:  retain this many held-out probe columns ``(A^T B) @ Omega``
             alongside the sketches (same single pass over the rows; the
             probe stage is backend-independent, so the probe block is
             bit-identical across backends for a fixed ``block``). Powers
             the ErrorEngine's ``estimate_error``/``adaptive_rank``.
    cosketch: retain an s-column Tropp range/co-range pair
             ``(A^T B) @ Omega_c`` / ``Psi_c @ (A^T B)`` alongside the
             sketches (same single pass; backend-independent attach like the
             probe block). Powers the RefinementEngine's sketch-power/Tropp
             refinement (``estimate_product(method='power')``).
    tuning:  optional ``repro.kernels.tuning.TuningSpec`` pinning kernel
             block configs (acted on by the pallas backend; layout-only, so
             results stay within float reassociation of the default).
    mesh/axis: required for backend='distributed' (rows sharded over axis).

    >>> import jax, jax.numpy as jnp
    >>> key = jax.random.PRNGKey(0)
    >>> A = jax.random.normal(key, (64, 8))
    >>> B = jax.random.normal(jax.random.fold_in(key, 1), (64, 6))
    >>> s = build_summary(key, A, B, 16, backend="scan", block=32)
    >>> (s.A_sketch.shape, s.B_sketch.shape, s.norm_A.shape, s.norm_B.shape)
    ((16, 8), (16, 6), (8,), (6,))
    >>> ref = build_summary(key, A, B, 16)          # reference backend
    >>> bool(jnp.allclose(s.A_sketch, ref.A_sketch, atol=1e-5))
    True
    """
    if method not in METHODS:
        raise ValueError(f"unknown sketch method {method!r} (use {METHODS})")
    if backend not in _BACKENDS:
        raise ValueError(
            f"unknown summary backend {backend!r} (use one of {backends()})")
    fn = _BACKENDS[backend]
    kw = dict(method=method, block=block, precision=precision, tuning=tuning)
    if backend == "distributed":
        kw.update(mesh=mesh, axis=axis)

    if A.ndim == 3:
        if B.ndim != 3 or A.shape[0] != B.shape[0]:
            raise ValueError(f"batched mode needs matching leading axes, got "
                             f"{A.shape} vs {B.shape}")
        if backend == "distributed":
            raise NotImplementedError(
                "batched mode is not supported for backend='distributed'")
        L = A.shape[0]
        keys = key if _is_key_stack(key, L) else jax.random.split(key, L)
        out = jax.vmap(lambda kk, a, b: fn(kk, a, b, k, **kw))(keys, A, B)
        if probes:
            from repro.core import error_engine
            out = jax.vmap(lambda kk, a, b, s: error_engine.attach_probes(
                s, kk, a, b, probes, block=block, precision=precision)
            )(keys, A, B, out)
        if cosketch:
            from repro.core import refinement
            out = jax.vmap(lambda kk, a, b, s: refinement.attach_cosketch(
                s, kk, a, b, cosketch, block=block, precision=precision)
            )(keys, A, B, out)
        return out
    out = fn(key, A, B, k, **kw)
    if probes:
        from repro.core import error_engine
        out = error_engine.attach_probes(out, key, A, B, probes, block=block,
                                         precision=precision)
    if cosketch:
        from repro.core import refinement
        out = refinement.attach_cosketch(out, key, A, B, cosketch,
                                         block=block, precision=precision)
    return out


def norms_only_summary(A: jax.Array, B: jax.Array) -> SketchSummary:
    """A ``SketchSummary`` with exact column norms and empty (0, n) sketches —
    LELA's first pass, all a norm-driven estimator (lela_waltmin) consumes."""
    norm_A = jnp.sqrt(jnp.sum(A.astype(jnp.float32) ** 2, axis=0))
    norm_B = jnp.sqrt(jnp.sum(B.astype(jnp.float32) ** 2, axis=0))
    return SketchSummary(jnp.zeros((0, A.shape[1]), jnp.float32),
                         jnp.zeros((0, B.shape[1]), jnp.float32),
                         norm_A, norm_B)


def summary_stage(spec, key: jax.Array, A: jax.Array, B: jax.Array,
                  tuning=None) -> SketchSummary:
    """The step-1 pass as a fusable stage driven by a declarative spec.

    ``spec`` is any object with the ``SketchSpec`` fields (method, backend,
    k, block, precision, probes, cosketch) — ``core.pipeline`` owns the
    concrete type;
    taking it duck-typed keeps this module import-free of the pipeline layer.
    Pure and traceable: the PipelineEngine composes it with the estimation
    and error stages inside ONE jitted executable. ``method='norms_only'``
    is the sketch-free LELA first pass (the key is unused). ``tuning``
    rides the plan (``PipelinePlan.tuning``), not the spec, so one spec
    hash serves every tuning.
    """
    if spec.method == "norms_only":
        return norms_only_summary(A, B)
    return build_summary(key, A, B, spec.k, method=spec.method,
                         backend=spec.backend, block=spec.block,
                         precision=spec.precision, probes=spec.probes,
                         cosketch=getattr(spec, "cosketch", 0),
                         tuning=tuning)


# ---------------------------------------------------------------------------
# Structured-product summaries (engine-owned; no caller builds these by hand)
# ---------------------------------------------------------------------------

def identity_product_summary(key: jax.Array, G: jax.Array, k: int, *,
                             axis: Optional[str] = None, n_workers: int = 1,
                             precision: Optional[str] = None) -> SketchSummary:
    """Summary of the structured product A^T B with A = vstack_w(I), i.e.
    G = sum_w G_w — the gradient-compression mapping. A's sketch is each
    worker's Pi slice itself and ||A_i|| = sqrt(W) analytically, so A is
    never materialized. G: (n1, n2) or stacked (L, n1, n2) (batched mode).

    Inside ``shard_map`` pass ``axis``: G is the worker-local summand and the
    psum over workers IS the paper's treeAggregate."""
    if G.ndim == 3:
        keys = (key if _is_key_stack(key, G.shape[0])
                else jax.random.split(key, G.shape[0]))
        return jax.vmap(
            lambda kk, g: identity_product_summary(
                kk, g, k, axis=axis, n_workers=n_workers, precision=precision)
        )(keys, G)
    n1, n2 = G.shape
    if axis is not None:
        pi_key = jax.random.fold_in(key, jax.lax.axis_index(axis))
    else:
        pi_key = key
    Gc = _cast(G, precision)
    # ONE operator for both sides: the (possibly precision-rounded) Pi that
    # contracts with G is also what A_sketch reports (A slice = I), keeping
    # the estimator's shared-Pi assumption intact under reduced precision
    Pi = _cast(gaussian_pi(pi_key, k, n1), precision).astype(Gc.dtype)
    A_sk = Pi.astype(jnp.float32)                               # A slice = I
    B_sk = jax.lax.dot_general(Pi, Gc,
                               dimension_numbers=(((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    nb2 = jnp.sum(Gc.astype(jnp.float32) ** 2, axis=0)
    if axis is not None:
        A_sk = jax.lax.psum(A_sk, axis)
        B_sk = jax.lax.psum(B_sk, axis)
        nb2 = jax.lax.psum(nb2, axis)
    return SketchSummary(
        A_sk, B_sk,
        jnp.full((n1,), jnp.sqrt(float(n_workers)), jnp.float32),
        jnp.sqrt(nb2))


def tap_pair_summary(key: jax.Array, X: jax.Array, Y: jax.Array, k: int, *,
                     precision: Optional[str] = None):
    """One-pass (Pi X, Pi Y, col-norms^2) over X, Y (T x n) for the gradient
    tap. Returns the raw tuple (As, Bs, na2, nb2) — taps carry squared norms
    so DP all-reduce stays a plain sum.

    Deliberately ONE fused contraction over the token dimension (not the
    scan backend): under pjit the T-sharded contraction emits exactly one
    (k x n) psum per output, where a scan-over-blocks makes GSPMD emit a
    partial all-reduce per block. Pi is (T, k), sharded like X, never stored."""
    T = X.shape[0]
    Pi = jax.random.normal(key, (T, k)) / jnp.sqrt(k)
    Xc, Yc = _cast(X, precision), _cast(Y, precision)
    As = _sketch_dot(Pi, Xc, precision)
    Bs = _sketch_dot(Pi, Yc, precision)
    na2 = jnp.sum(Xc.astype(jnp.float32) ** 2, axis=0)
    nb2 = jnp.sum(Yc.astype(jnp.float32) ** 2, axis=0)
    return As, Bs, na2, nb2
