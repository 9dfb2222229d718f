"""RefinementEngine tests: Tropp co-sketch block + sketch-power refinement.

The contract (docs/estimation.md "Refined reconstruction"):

* the co-sketch pair (Y, W) is EXACTLY ((A^T B) omega, psi (A^T B)) — linear
  in the streamed rows, so it rides the streaming monoid (merge laws below)
  and the one-shot builder bit-for-bit;
* refined factorizations are never worse than the raw rescaled-sketch
  truncation at equal rank (the parity matrix), and the quality gate
  (``adaptive_rank``) passes at strictly lower rank on a slow spectrum —
  the acceptance criterion of the refinement PR;
* ``cosketch=0`` (the default) is bit-identical to the pre-refinement
  engine: no new pytree leaves, same treedef, same values.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from tests._hyp import given, settings
    from tests._hyp import strategies as st

from repro import core
from repro.core import error_engine, estimation_engine, refinement, streaming
from repro.core.refinement import RefineSpec
from repro.core.summary_engine import build_summary
from tests.conftest import (
    drifting_spectrum_pair, gaussian_pair, known_spectrum_pair,
    spectrum_values)


def _spectral_err(A, B, factors):
    M = np.asarray(A.T @ B)
    approx = np.asarray(factors.U) @ np.asarray(factors.V).T
    return (np.linalg.norm(M - approx, ord=2)
            / np.linalg.norm(M, ord=2))


# ---------------------------------------------------------------------------
# The co-sketch block is exact and deterministic
# ---------------------------------------------------------------------------

def test_cosketch_block_is_exact(key):
    """Y == (A^T B) omega and W == psi (A^T B) to float tolerance, with the
    test matrices drawn from the reserved "csk!" fold of the base key."""
    A, B = gaussian_pair(key)
    s = build_summary(key, A, B, 16, cosketch=5)
    M = np.asarray(A.T @ B)
    omega = np.asarray(s.cosketch_omega)
    psi = np.asarray(s.cosketch_psi)
    assert omega.shape == (7, 5)
    assert psi.shape == (refinement.cosketch_width(5), 11)
    np.testing.assert_allclose(np.asarray(s.cosketch_Y), M @ omega,
                               rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s.cosketch_W), psi @ M,
                               rtol=2e-4, atol=1e-4)
    assert s.n_cosketch == 5


def test_cosketch_off_is_bit_identical_legacy(key):
    """cosketch=0 (the default) adds no pytree leaves: same treedef, same
    leaf values as the pre-refinement engine produced."""
    A, B = gaussian_pair(key)
    with_off = build_summary(key, A, B, 16)
    assert with_off.cosketch_Y is None and with_off.cosketch_W is None
    assert with_off.cosketch_omega is None and with_off.cosketch_psi is None
    assert with_off.n_cosketch == 0
    # None fields are not leaves: the treedef/leaf count is the legacy one
    leaves = jax.tree_util.tree_leaves(with_off)
    assert len(leaves) == 4
    # and a cosketch-carrying build leaves the legacy block bit-untouched
    with_on = build_summary(key, A, B, 16, cosketch=3)
    for name in ("A_sketch", "B_sketch", "norm_A", "norm_B"):
        np.testing.assert_array_equal(
            np.asarray(getattr(with_off, name)),
            np.asarray(getattr(with_on, name)), err_msg=name)


def test_refine_spec_validation():
    with pytest.raises(TypeError, match="RefineSpec"):
        refinement.validate_refine((1, "tropp"))
    with pytest.raises(ValueError, match="method"):
        refinement.validate_refine(RefineSpec(1, "qr"))
    with pytest.raises(ValueError, match="iters"):
        refinement.validate_refine(RefineSpec(-1, "power"))
    with pytest.raises(ValueError, match="iters"):
        refinement.validate_refine(RefineSpec(True, "power"))
    refinement.validate_refine(RefineSpec())          # defaults are valid


def test_estimate_product_power_guards(key):
    """method='power' needs a co-sketch-carrying summary; refine= rejects
    other methods eagerly (never a silent ignore)."""
    A, B = gaussian_pair(key)
    bare = build_summary(key, A, B, 16)
    with pytest.raises(ValueError, match="co-sketch"):
        estimation_engine.estimate_product(key, bare, 2, method="power")
    with pytest.raises(ValueError, match="refine"):
        estimation_engine.estimate_product(
            key, bare, 2, m=64, T=2, refine=RefineSpec(1, "power"))


# ---------------------------------------------------------------------------
# Refinement parity matrix: refined never worse at equal rank
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["fast", "slow"])
@pytest.mark.parametrize("spec", [RefineSpec(0, "tropp"),
                                  RefineSpec(2, "power")])
def test_refined_not_worse_at_equal_rank(key, kind, spec):
    """On the known-spectrum fixtures, the refined factorization's spectral
    error at rank r is never worse than the raw rescaled-sketch truncation
    (direct_svd) at the same rank — across both refinement methods."""
    A, B, _ = known_spectrum_pair(key, 384, 14, 12, spectrum_values(kind))
    summary = build_summary(key, A, B, 48, cosketch=10)
    for r in (3, 6):
        raw = estimation_engine.estimate_product(
            key, summary, r, method="direct_svd")
        ref = estimation_engine.estimate_product(
            key, summary, r, method="power", refine=spec)
        e_raw = _spectral_err(A, B, raw.factors)
        e_ref = _spectral_err(A, B, ref.factors)
        assert e_ref <= e_raw * 1.02 + 1e-4, \
            (kind, spec, r, e_ref, e_raw)


def test_refined_not_worse_on_drifting_phases(key):
    """Same parity on both phases of the drifting-stream fixture (disjoint
    top subspaces, exact low rank): refined recovers each phase's product
    at least as well as the raw truncation."""
    (A1, B1, _, _), (A2, B2, _, _) = drifting_spectrum_pair(key)
    for A, B in ((A1, B1), (A2, B2)):
        summary = build_summary(key, A, B, 48, cosketch=8)
        raw = estimation_engine.estimate_product(
            key, summary, 3, method="direct_svd")
        ref = estimation_engine.estimate_product(
            key, summary, 3, method="power", refine=RefineSpec(0, "tropp"))
        assert _spectral_err(A, B, ref.factors) <= \
            _spectral_err(A, B, raw.factors) * 1.02 + 1e-4


def test_power_iterations_tighten_tight_retention(key):
    """In the tight-retention regime (co-sketch width barely above the
    target rank, decaying spectrum) sketch-power iterations buy real
    accuracy: err(iters=2) is clearly below err(iters=0). This is the
    retained-bytes-vs-accuracy trade the power method exists for."""
    A, B, _ = known_spectrum_pair(key, 384, 14, 12, spectrum_values("slow"))
    summary = build_summary(key, A, B, 128, cosketch=6)
    errs = []
    for iters in (0, 2):
        est = estimation_engine.estimate_product(
            key, summary, 3, method="power",
            refine=RefineSpec(iters, "power"))
        errs.append(_spectral_err(A, B, est.factors))
    assert errs[1] < errs[0] * 0.8, errs


# ---------------------------------------------------------------------------
# The acceptance pin: the auto-rank gate passes at lower rank
# ---------------------------------------------------------------------------

def test_adaptive_rank_passes_at_lower_rank_slow_spectrum(key):
    """THE acceptance criterion: on the slow-decay known-spectrum fixture,
    quality-gated rank selection with Tropp refinement meets tol=0.3 at a
    STRICTLY smaller rank than the unrefined gate, and the refined pick is
    honest (its true spectral error is consistent with the tolerance
    regime). Power refinement is never worse than unrefined."""
    A, B, _ = known_spectrum_pair(key, 384, 14, 12, spectrum_values("slow"))
    summary = build_summary(key, A, B, 48, probes=24, cosketch=10)
    plain = error_engine.adaptive_rank(summary, tol=0.3)
    tropp = error_engine.adaptive_rank(summary, tol=0.3,
                                       refine=RefineSpec(0, "tropp"))
    power = error_engine.adaptive_rank(summary, tol=0.3,
                                       refine=RefineSpec(1, "power"))
    assert tropp.r < plain.r, (tropp.r, plain.r)
    assert power.r <= plain.r, (power.r, plain.r)
    # the refined gate is not a free lunch: its factors really are that good
    assert _spectral_err(A, B, tropp.factors) < \
        _spectral_err(A, B, plain.factors) * 1.02 + 1e-4
    # and the refined curve sits at or below the raw curve where both exist
    n = min(tropp.curve.shape[0], plain.curve.shape[0])
    assert float(jnp.mean(tropp.curve[:n] - plain.curve[:n])) <= 1e-3


def test_rank_curve_refined_capped_by_cosketch_width(key):
    """The refined basis has only s columns, so the refined curve is capped
    at s even when r_max asks for more."""
    A, B = gaussian_pair(key)
    summary = build_summary(key, A, B, 16, probes=6, cosketch=4)
    curve = error_engine.rank_curve(summary, 7, refine=RefineSpec(0, "tropp"))
    assert curve.shape[0] == 4
    assert error_engine.rank_curve(summary, 7).shape[0] == 7
    bare = build_summary(key, A, B, 16, probes=6)
    with pytest.raises(ValueError, match="co-sketch"):
        error_engine.adaptive_rank(bare, tol=0.5, refine=RefineSpec())


# ---------------------------------------------------------------------------
# The co-sketch block rides the streaming monoid
# ---------------------------------------------------------------------------

def _cosketch_close(got, want, rtol=2e-4):
    for name in ("cosketch_Y", "cosketch_W"):
        g, w = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        np.testing.assert_allclose(
            g, w, rtol=rtol, atol=1e-5 * max(np.abs(w).max(), 1.0),
            err_msg=name)


def test_streaming_cosketch_bit_identical_to_scan(key):
    """Sequential chunked ingestion with a co-sketch block == the scan
    backend at the same block size, bit-for-bit — including Y and W."""
    A, B = gaussian_pair(key, d=256)
    summ = core.StreamingSummarizer(16, probes=3, cosketch=4)
    state = summ.init(key, (256, 11, 7))
    for off in range(0, 256, 64):
        state = summ.update(state, A[off:off + 64], B[off:off + 64], off)
    got = summ.finalize(state)
    want = build_summary(key, A, B, 16, backend="scan", block=64,
                         probes=3, cosketch=4)
    for name in ("A_sketch", "B_sketch", "norm_A", "norm_B",
                 "cosketch_Y", "cosketch_W", "cosketch_omega",
                 "cosketch_psi"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            err_msg=name)


def test_cosketch_merge_commutative_bitwise(key):
    A, B = gaussian_pair(key)
    summ = core.StreamingSummarizer(8, cosketch=3)
    empty = summ.init(key, (192, 11, 7))
    s1 = summ.update(empty, A[:96], B[:96], 0)
    s2 = summ.update(empty, A[96:], B[96:], 96)
    m12, m21 = summ.merge(s1, s2), summ.merge(s2, s1)
    for f in ("cosketch_Y", "cosketch_W"):
        np.testing.assert_array_equal(np.asarray(getattr(m12, f)),
                                      np.asarray(getattr(m21, f)), err_msg=f)


@settings(deadline=None, max_examples=8)
@given(i=st.sampled_from([32, 64, 96]), j=st.sampled_from([128, 160]))
def test_cosketch_merge_associative_property(i, j):
    """finalize(merge(merge(a,b),c)) ~= finalize(merge(a,merge(b,c))) on the
    co-sketch accumulators for arbitrary three-way splits (property test)."""
    key = jax.random.PRNGKey(3)
    A, B = gaussian_pair(key)
    summ = core.StreamingSummarizer(8, cosketch=3)
    empty = summ.init(key, (192, 11, 7))
    a = summ.update(empty, A[:i], B[:i], 0)
    b = summ.update(empty, A[i:j], B[i:j], i)
    c = summ.update(empty, A[j:], B[j:], j)
    left = summ.finalize(summ.merge(summ.merge(a, b), c))
    right = summ.finalize(summ.merge(a, summ.merge(b, c)))
    _cosketch_close(left, right, rtol=2e-5)


@settings(deadline=None, max_examples=6)
@given(chunk=st.sampled_from([32, 64, 96]), order_seed=st.integers(0, 99))
def test_cosketch_any_merge_order_matches_one_shot(chunk, order_seed):
    """Per-chunk partial states merged in a random order reproduce the
    one-shot co-sketch block (property test)."""
    key = jax.random.PRNGKey(4)
    A, B = gaussian_pair(key)
    summ = core.StreamingSummarizer(8, cosketch=3)
    empty = summ.init(key, (192, 11, 7))
    parts = [summ.update(empty, A[off:off + chunk], B[off:off + chunk], off)
             for off in range(0, 192, chunk)]
    rng = np.random.default_rng(order_seed)
    rng.shuffle(parts)
    merged = parts[0]
    for p in parts[1:]:
        merged = streaming.merge_states(merged, p)
    _cosketch_close(summ.finalize(merged),
                    build_summary(key, A, B, 8, cosketch=3))


def test_cosketch_presence_mismatch_rejected(key):
    """Merging a co-sketch-carrying state with a co-sketch-free one is a
    descriptive ValueError, not a silent drop — in both engines."""
    A, B = gaussian_pair(key)
    with_c = core.StreamingSummarizer(8, cosketch=3)
    without = core.StreamingSummarizer(8)
    sa = with_c.update(with_c.init(key, (192, 11, 7)), A[:96], B[:96], 0)
    sb = without.update(without.init(key, (192, 11, 7)), A[96:], B[96:], 96)
    with pytest.raises(ValueError, match="cosketch"):
        streaming.merge_states(sa, sb)
    from repro.core.sketch import merge_summaries
    with pytest.raises(ValueError, match="cosketch"):
        merge_summaries(build_summary(key, A, B, 8, cosketch=3),
                        build_summary(key, A, B, 8))


def test_merged_summaries_cosketch_matches_full_build(key):
    """merge_summaries on row-split one-shot summaries reproduces the full
    build's co-sketch block (the SketchSummary-level monoid)."""
    from repro.core.sketch import merge_summaries
    A, B = gaussian_pair(key, d=256)
    full = build_summary(key, A, B, 16, cosketch=4)
    top = build_summary(key, A[:128], B[:128], 16, cosketch=4)
    # bottom half must sketch with its GLOBAL row ids
    bot_state = core.StreamingSummarizer(16, cosketch=4).init(
        key, (256, 11, 7))
    bot_state = core.StreamingSummarizer(16, cosketch=4).update(
        bot_state, A[128:], B[128:], 128)
    bot = streaming.finalize_state(bot_state)
    # top half as a summary has rows 0..128 at the same global ids
    _cosketch_close(merge_summaries(top, bot), full)


def test_decayed_and_windowed_sessions_carry_cosketch(key):
    """Drifting-stream variants keep the block consistent: a decayed state
    scales Y/W with the sketches, and window buckets share the BASE key's
    (omega, psi) pair so expired epochs drop out linearly."""
    A, B = gaussian_pair(key, d=128)
    dec = core.StreamingSummarizer(8, cosketch=3, decay=0.5)
    st_ = dec.init(key, (128, 11, 7))
    st_ = dec.update(st_, A[:64], B[:64], 0)
    st_ = dec.advance(st_, 1)
    st_ = dec.update(st_, A[64:], B[64:], 64)
    s = dec.finalize(st_)
    # decayed Y == 0.5 * Y(first half) + Y(second half), like the sketches
    s1 = dec.finalize(dec.update(dec.init(key, (128, 11, 7)),
                                 A[:64], B[:64], 0))
    s2 = dec.finalize(dec.update(dec.init(key, (128, 11, 7)),
                                 A[64:], B[64:], 64))
    np.testing.assert_allclose(
        np.asarray(s.cosketch_Y),
        0.5 * np.asarray(s1.cosketch_Y) + np.asarray(s2.cosketch_Y),
        rtol=2e-5, atol=1e-5)

    win = core.WindowedSummarizer(8, 2, cosketch=3)
    w = win.init(key, (64, 11, 7))
    base_omega = w.buckets[0].cosketch_omega
    w = win.update(w, A[:64], B[:64], 0)
    w = win.slide(w, 2)                        # first epoch fully expired
    w = win.update(w, A[64:128], B[64:128], 0)
    got = win.finalize(w)
    # every bucket shares the base pair ...
    for b in w.buckets:
        np.testing.assert_array_equal(np.asarray(b.cosketch_omega),
                                      np.asarray(base_omega))
    # ... so the finalized window equals the live rows' exact co-sketch —
    # the expired epoch's contribution dropped out linearly
    np.testing.assert_allclose(
        np.asarray(got.cosketch_Y),
        np.asarray(A[64:128].T @ (B[64:128] @ base_omega)),
        rtol=2e-4, atol=1e-4)


def test_stream_state_checkpoint_roundtrip_with_cosketch(key, tmp_path):
    """save_stream_state/restore_stream_state round-trip the co-sketch
    accumulators bit-exactly and record the width in the manifest."""
    from repro.ckpt import checkpoint
    A, B = gaussian_pair(key, d=128)
    summ = core.StreamingSummarizer(8, cosketch=3)
    state = summ.update(summ.init(key, (128, 11, 7)), A[:64], B[:64], 0)
    checkpoint.save_stream_state(str(tmp_path), 1, state)
    assert checkpoint.read_manifest(str(tmp_path))["extra"]["cosketch"] == 3
    like = summ.init(key, (128, 11, 7))
    back = checkpoint.restore_stream_state(str(tmp_path), like)
    back = summ.update(back, A[64:], B[64:], 64)
    state = summ.update(state, A[64:], B[64:], 64)
    for f in ("cosketch_Y", "cosketch_W", "A_acc"):
        np.testing.assert_array_equal(np.asarray(getattr(back, f)),
                                      np.asarray(getattr(state, f)), err_msg=f)


# ---------------------------------------------------------------------------
# Plan/serving integration: refine joins the cache key
# ---------------------------------------------------------------------------

def test_pipeline_refine_joins_cache_key(key):
    """Two plans differing only in RefineSpec compile separately; repeat
    traffic under a pinned refinement never re-traces."""
    from repro.core import pipeline
    A, B = gaussian_pair(key, d=128)
    eng = pipeline.PipelineEngine()
    mk = lambda spec: pipeline.PipelinePlan(
        sketch=pipeline.SketchSpec(k=16, cosketch=4),
        estimation=pipeline.EstimationSpec(method="power", backend="jit"),
        rank=pipeline.RankPolicy(r=2), refine=spec)
    r0 = eng.run(mk(RefineSpec(0, "tropp")), key, A, B)
    assert eng.stats.misses == 1
    eng.run(mk(RefineSpec(2, "power")), key, A, B)
    assert eng.stats.misses == 2                      # distinct executable
    eng.run(mk(RefineSpec(0, "tropp")), key, A, B)
    assert (eng.stats.hits, eng.stats.traces) == (1, 2)   # warm: no re-trace
    assert r0.estimate.factors.U.shape == (11, 2)


def test_service_stream_refined_matches_one_shot(key):
    """stream_factors with a co-sketch-carrying service reproduces one-shot
    flush_factors under method='power' + refine. The summaries are
    bit-identical; the flush estimates a batch of one under vmap, which
    XLA may fuse differently, so the factors agree to f32 rounding."""
    from repro.serve.engine import SketchService
    A, B = gaussian_pair(key, d=64)
    svc = SketchService(k=8, backend="scan", block=32, cosketch=3)
    t = svc.submit(key, A, B)
    served = svc.flush_factors(r=2, est_method="power",
                               refine=RefineSpec(1, "power"))[t]
    sid = svc.open_stream(key, 64, 11, 7)
    svc.append(sid, A[:32], B[:32])
    svc.append(sid, A[32:], B[32:])
    est = svc.stream_factors(sid, r=2, est_method="power",
                             refine=RefineSpec(1, "power"))
    for got, want in ((est.factors.U, served.factors.U),
                      (est.factors.V, served.factors.V)):
        want = np.asarray(want)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())


def test_batched_power_estimation(key):
    """The vmapped service path handles method='power': stacked summaries
    yield stacked refined factors equal to the per-pair runs."""
    keys = jnp.stack([key, jax.random.fold_in(key, 7)])
    A = jax.random.normal(key, (2, 64, 6))
    B = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 5))
    summary = build_summary(keys, A, B, 8, cosketch=3)
    est = estimation_engine.estimate_product(
        keys, summary, 2, method="power", refine=RefineSpec(1, "power"))
    assert est.factors.U.shape == (2, 6, 2)
    solo = build_summary(keys[1], A[1], B[1], 8, cosketch=3)
    one = estimation_engine.estimate_product(
        keys[1], solo, 2, method="power", refine=RefineSpec(1, "power"))
    np.testing.assert_allclose(np.asarray(est.factors.U[1]),
                               np.asarray(one.factors.U),
                               rtol=2e-5, atol=1e-5)
