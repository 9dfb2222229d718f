"""PipelineEngine: plan hashing/validation, executable-cache behavior
(hits, zero-retrace warm paths, LRU eviction), plan-path parity with the
stage-by-stage composition, and the single-sweep quality gate's dispatch
accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import estimation_engine, pipeline, summary_engine
from repro.core.pipeline import (
    EstimationSpec, PipelineEngine, PipelinePlan, RankPolicy, SketchSpec)
from repro.serve.engine import SketchService

from tests.conftest import gaussian_pair, known_spectrum_pair


def _service(k=8, probes=0, engine=None):
    return SketchService(k=k, backend="scan", block=32, probes=probes,
                         engine=engine)


def _submit_bucketed(svc, key, shapes):
    """One request per (d, n) shape; same-shape entries share a bucket."""
    tickets = []
    for i, (d, n) in enumerate(shapes):
        kk = jax.random.fold_in(key, i)
        A = jax.random.normal(kk, (d, n))
        B = jax.random.normal(jax.random.fold_in(kk, 99), (d, n))
        tickets.append(svc.submit(kk, A, B))
    return tickets


# ---------------------------------------------------------------------------
# Plans
# ---------------------------------------------------------------------------

def test_plans_are_hashable_and_value_keyed():
    p1 = pipeline.smppca_plan(r=2, k=16, m=200, T=2)
    p2 = pipeline.smppca_plan(r=2, k=16, m=200, T=2)
    p3 = pipeline.smppca_plan(r=2, k=16, m=200, T=3)
    assert hash(p1) == hash(p2) and p1 == p2
    assert p1 != p3
    assert len({p1, p2, p3}) == 2


def test_plan_validation_errors(key):
    eng = PipelineEngine()
    A, B = gaussian_pair(key, d=32, n1=4, n2=3)
    bad = [
        (PipelinePlan(key_layout="nope", rank=RankPolicy(r=2)), "layout"),
        (PipelinePlan(sketch=SketchSpec(method="nope"),
                      rank=RankPolicy(r=2)), "sketch method"),
        (PipelinePlan(sketch=SketchSpec(backend="nope"),
                      rank=RankPolicy(r=2)), "summary backend"),
        (PipelinePlan(sketch=SketchSpec(backend="distributed"),
                      rank=RankPolicy(r=2)), "distributed"),
        (PipelinePlan(estimation=EstimationSpec(method="nope"),
                      rank=RankPolicy(r=2)), "estimation method"),
        (PipelinePlan(estimation=EstimationSpec(backend="nope"),
                      rank=RankPolicy(r=2)), "estimation backend"),
        (PipelinePlan(rank=RankPolicy(r=None, tol=None)), "tol"),
        (PipelinePlan(rank=RankPolicy(r=None, tol=0.5)), "probe"),
        (PipelinePlan(rank=RankPolicy(r=2.5)), "int"),
        (PipelinePlan(rank=RankPolicy(r=2), with_error=True), "probes"),
    ]
    for plan, match in bad:
        with pytest.raises(ValueError, match=match):
            eng.run(plan, key, A, B)
    with pytest.raises(TypeError, match="PipelinePlan"):
        eng.run("not a plan", key, A, B)
    with pytest.raises(ValueError, match="max_entries"):
        PipelineEngine(max_entries=0)


# ---------------------------------------------------------------------------
# Plan-path parity with the stage-by-stage composition
# ---------------------------------------------------------------------------

def test_run_matches_stagewise_composition_bitwise(key):
    """engine.run(smppca preset) == build_summary + estimate_product with
    smppca's historical key fan-out, bit-for-bit."""
    A, B = gaussian_pair(key, d=96, n1=10, n2=8)
    eng = PipelineEngine()
    res = eng.run(pipeline.smppca_plan(r=2, k=16, m=200, T=2), key, A, B)
    k_sketch, k_sample, _ = jax.random.split(key, 3)
    summary = summary_engine.build_summary(k_sketch, A, B, 16)
    manual = estimation_engine.estimate_product(
        jax.random.fold_in(k_sample, 0), summary, 2, m=200, T=2)
    np.testing.assert_array_equal(np.asarray(res.estimate.factors.U),
                                  np.asarray(manual.factors.U))
    np.testing.assert_array_equal(np.asarray(res.estimate.factors.V),
                                  np.asarray(manual.factors.V))
    np.testing.assert_array_equal(np.asarray(res.summary.A_sketch),
                                  np.asarray(summary.A_sketch))


def test_run_from_summary_matches_estimate_product_bitwise(key):
    """The compiled from-summary path (stream_factors' spine) derives the
    service fold_in(key, 1) estimation key and matches estimate_product."""
    A, B = gaussian_pair(key, d=96, n1=10, n2=8)
    summary = summary_engine.build_summary(key, A, B, 16)
    eng = PipelineEngine()
    plan = PipelinePlan(sketch=SketchSpec(k=16, backend="scan"),
                        estimation=EstimationSpec(m=200, T=2),
                        rank=RankPolicy(r=2), key_layout="service")
    est = eng.run_from_summary(plan, key, summary)
    manual = estimation_engine.estimate_product(
        jax.random.fold_in(key, 1), summary, 2, m=200, T=2)
    np.testing.assert_array_equal(np.asarray(est.factors.U),
                                  np.asarray(manual.factors.U))


def test_summarize_matches_build_summary_bitwise(key):
    A, B = gaussian_pair(key, d=64, n1=6, n2=5)
    eng = PipelineEngine()
    spec = SketchSpec(method="srht", backend="scan", k=8, block=32)
    got = eng.summarize(spec, key, A, B)
    want = summary_engine.build_summary(key, A, B, 8, method="srht",
                                        backend="scan", block=32)
    for name in ("A_sketch", "B_sketch", "norm_A", "norm_B"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)))


# ---------------------------------------------------------------------------
# Executable cache: warm hits, zero retraces, one fused dispatch per bucket
# ---------------------------------------------------------------------------

def test_warm_flush_factors_one_fused_dispatch_zero_retraces(key):
    """The acceptance gate: a repeated-shape warm flush_factors performs
    exactly ONE fused dispatch per shape bucket with ZERO new traces."""
    eng = PipelineEngine()
    svc = _service(engine=eng)
    shapes = [(64, 6), (96, 5), (64, 6)]          # two buckets, one repeated
    t_cold = _submit_bucketed(svc, key, shapes)
    cold = svc.flush_factors(r=2, m=100, T=2)
    traces0 = eng.stats.traces
    assert traces0 == 2                           # one trace per shape bucket
    assert eng.stats.est_dispatches == 2          # ... and one fused dispatch
    assert eng.stats.curve_dispatches == 0        # fully fused: no extra stage

    t_warm = _submit_bucketed(svc, key, shapes)   # same keys, same shapes
    warm = svc.flush_factors(r=2, m=100, T=2)
    assert eng.stats.traces == traces0            # ZERO new traces
    assert eng.stats.est_dispatches == 4          # one fused dispatch/bucket
    assert eng.stats.hits == 2
    for tc, tw in zip(t_cold, t_warm):            # warm == cold, bit-for-bit
        np.testing.assert_array_equal(np.asarray(cold[tc].factors.U),
                                      np.asarray(warm[tw].factors.U))


def test_distinct_plans_never_share_entries(key):
    """Plans differing in any field get their own executables (and differing
    shapes get their own signatures under one plan)."""
    eng = PipelineEngine()
    svc = _service(engine=eng)
    _submit_bucketed(svc, key, [(64, 6)])
    svc.flush_factors(r=2, m=100, T=2)
    _submit_bucketed(svc, key, [(64, 6)])
    svc.flush_factors(r=3, m=100, T=2)            # different rank -> new entry
    _submit_bucketed(svc, key, [(64, 6)])
    svc.flush_factors(r=2, m=100, T=3)            # different T -> new entry
    assert eng.stats.misses == 3 and eng.stats.hits == 0
    assert len(eng) == 3
    _submit_bucketed(svc, key, [(48, 6)])         # same plan, new shape
    svc.flush_factors(r=2, m=100, T=2)
    assert eng.stats.misses == 4 and len(eng) == 4


def test_cache_eviction_at_lru_bound(key):
    """Past max_entries the least-recently-used executable is dropped and
    re-traced on next use."""
    eng = PipelineEngine(max_entries=2)
    svc = _service(engine=eng)

    def flush_shape(d):
        _submit_bucketed(svc, key, [(d, 6)])
        svc.flush_factors(r=2, m=100, T=2)

    flush_shape(32)
    flush_shape(48)
    assert eng.stats.evictions == 0 and len(eng) == 2
    flush_shape(64)                               # evicts the (32, 6) entry
    assert eng.stats.evictions == 1 and len(eng) == 2
    traces0 = eng.stats.traces
    flush_shape(48)                               # still cached: no retrace
    assert eng.stats.traces == traces0 and eng.stats.hits == 1
    flush_shape(32)                               # evicted: must retrace
    assert eng.stats.traces == traces0 + 1
    assert eng.stats.evictions == 2


def test_engine_clear_drops_executables(key):
    eng = PipelineEngine()
    svc = _service(engine=eng)
    _submit_bucketed(svc, key, [(64, 6)])
    svc.flush_factors(r=2, m=100, T=2)
    assert len(eng) == 1
    eng.clear()
    assert len(eng) == 0
    _submit_bucketed(svc, key, [(64, 6)])
    svc.flush_factors(r=2, m=100, T=2)
    assert eng.stats.traces == 2                  # cleared -> re-traced


# ---------------------------------------------------------------------------
# Quality-gated path: single-sweep gate, one estimation dispatch per bucket
# ---------------------------------------------------------------------------

def test_gated_flush_single_estimation_dispatch(key):
    """Regression for the per-round escalation: a gated flush is one curve
    dispatch + ONE estimation dispatch per bucket, however many ranks the
    doubling schedule probes — and a warm gated flush never retraces."""
    A, B, _ = known_spectrum_pair(
        key, 384, 14, 12, jnp.array([16.0, 12.0, 8.0, 6.0, 4.0, 3.0,
                                     0.05, 0.02]))
    eng = PipelineEngine()
    svc = _service(k=512, probes=24, engine=eng)
    svc.submit(key, A, B)
    svc.submit(jax.random.fold_in(key, 7), A, B)
    out = svc.flush_factors(r="auto", tol=0.2, m=1500, T=4,
                            est_method="direct_svd")
    assert eng.stats.curve_dispatches == 1        # ONE rank-curve sweep
    assert eng.stats.est_dispatches == 1          # ONE estimation dispatch
    assert all(v.factors.r >= 8 for v in out.values())   # it did escalate
    traces0 = eng.stats.traces
    svc.submit(key, A, B)
    svc.submit(jax.random.fold_in(key, 7), A, B)
    svc.flush_factors(r="auto", tol=0.2, m=1500, T=4, est_method="direct_svd")
    assert eng.stats.traces == traces0            # warm gate: zero retraces
    assert (eng.stats.curve_dispatches, eng.stats.est_dispatches) == (2, 2)


def test_gated_served_estimate_is_authoritative(key):
    """The curve only fast-forwards the schedule; the SERVED factors'
    a-posteriori estimate has the final word. With a starved completion
    (tiny m, T=1) the SVD-truncation curve passes rank 4 but the WAltMin
    factors miss tol there — the gate must keep escalating."""
    A, B, _ = known_spectrum_pair(
        key, 384, 14, 12, jnp.array([16.0, 12.0, 8.0, 6.0, 4.0, 3.0,
                                     0.05, 0.02]))
    eng = PipelineEngine()
    svc = _service(k=512, probes=24, engine=eng)
    t = svc.submit(key, A, B)
    out = svc.flush_factors(r="auto", tol=0.3, r_max=8, m=300, T=1)[t]
    # curve (rank-4 value ~0.25) picked 4; the served estimate (~0.37) failed
    # the gate, so the schedule doubled to the cap
    assert out.factors.r == 8
    assert eng.stats.curve_dispatches == 1
    assert eng.stats.est_dispatches == 2          # one escalation round
    assert float(out.error.rel_est) > 0.3         # honest at the cap


def test_gated_curve_executable_shared_across_tolerances(key):
    """tol is consumed host-side: gated flushes differing only in tol share
    one compiled curve sweep (only a new rank's estimation executable may
    trace)."""
    A, B, _ = known_spectrum_pair(
        key, 384, 14, 12, jnp.array([16.0, 12.0, 8.0, 6.0, 4.0, 3.0,
                                     0.05, 0.02]))
    # Each tol sits well clear of the 24-probe estimate's noise (about 15%
    # of the error) around the true errors: 0.218 at rank 4, and the k=512
    # sketch-noise floor of about 0.14 from rank 7 on. tol=0.3 passes at
    # rank 4; tol=0.05 fails every rank, so the schedule runs to the cap.
    eng = PipelineEngine()
    svc = _service(k=512, probes=24, engine=eng)
    svc.submit(key, A, B)
    out = svc.flush_factors(r="auto", tol=0.3, m=1500, T=4,
                            est_method="direct_svd")
    assert out[0].factors.r == 4
    assert eng.stats.traces == 2                  # one curve + one est trace
    svc.submit(key, A, B)
    out = svc.flush_factors(r="auto", tol=0.05, m=1500, T=4,
                            est_method="direct_svd")
    assert out[1].factors.r == 12                 # min(n1, n2, k)
    assert eng.stats.traces == 3                  # curve shared; new rank only
    assert eng.stats.curve_dispatches == 2 and eng.stats.misses == 3
    svc.submit(key, A, B)
    svc.flush_factors(r="auto", tol=0.05, m=1500, T=4,
                      est_method="direct_svd")
    assert eng.stats.traces == 3                  # fully warm


def test_gated_rank_curve_matches_adaptive_rank_sweep(key):
    """The gate's curve is the adaptive_rank sweep: same single-SVD relative
    error curve, read through the public rank_curve stage."""
    A, B, _ = known_spectrum_pair(key, 256, 12, 10, jnp.array(
        [8.0, 4.0, 2.0, 1.0, 0.5, 0.1, 0.05, 0.02, 0.01, 0.005]))
    summary = core.build_summary(key, A, B, 64, probes=16)
    curve = core.rank_curve(summary, 8)
    res = core.adaptive_rank(summary, tol=0.3, r_max=8)
    np.testing.assert_array_equal(np.asarray(curve), np.asarray(res.curve))


def test_rank_curve_requires_probes(key):
    A, B = gaussian_pair(key, d=64, n1=6, n2=5)
    with pytest.raises(ValueError, match="probe"):
        core.rank_curve(core.build_summary(key, A, B, 8), 4)
