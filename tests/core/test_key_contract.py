"""Golden key-derivation regression tests.

The last PRs promised a bit-for-bit randomness contract: every engine
derives its PRNG keys from the caller's base key through FIXED fold_in/split
trees (documented in docs/architecture.md "Where the randomness lives").
These tests freeze that tree as hard-coded uint32 key data for
``PRNGKey(0)`` — a refactor that silently moves a split or fold_in now fails
here instead of invisibly invalidating every reproducibility claim.

Golden values were recorded from the jax threefry2x32 PRNG (the default)
under ``jax_threefry_partitionable=True``, the default of the installed jax
line. That flag changes what ``split`` returns (with it on,
``split(key, n)[i] == fold_in(key, i)``), so the split-derived goldens hold
for that setting only; ``fold_in`` values are the same under both. Each
test ALSO checks the public entry point consumes the derived key
(composition equality), so the goldens pin behavior, not just
documentation.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.core import estimation_engine, summary_engine
from repro.core.error_engine import probe_key, probe_omega

KEY0 = [0, 0]                     # PRNGKey(0) raw key data

# split(PRNGKey(0), 3) — smppca's (k_sketch, k_sample, k_als) layout
SMPPCA_SPLIT3 = [[1797259609, 2579123966],
                 [928981903, 3453687069],
                 [4146024105, 2718843009]]
# fold_in(k_sample, 0) — the key smppca hands to estimate_product
SMPPCA_EST_KEY = [346279018, 360566543]
# split(SMPPCA_EST_KEY) — estimation's (sample key, ALS key)
EST_SPLIT2 = [[2067023257, 4011464742], [282707546, 681628770]]

# fold_in(PRNGKey(0), i) — the per-row gaussian projection keys
ROW_KEYS = {0: [1797259609, 2579123966],
            1: [928981903, 3453687069],
            5: [1524306142, 1887795613]}

# split(PRNGKey(0)) — srht_plan's (sign key, row-sample key); sketch_svd and
# estimate_product share the same single split of their own base key
SPLIT2 = [[1797259609, 2579123966], [928981903, 3453687069]]

# fold_in(PRNGKey(0), 1) — SketchService's per-request estimation key
SERVICE_EST_KEY = [928981903, 3453687069]

# fold_in(fold_in(PRNGKey(0), 0x70726F62), 0x6521) — the ErrorEngine's
# reserved two-level probe fold ("prob", "e!")
PROBE_KEY = [3361526193, 307077598]

# fold_in(PRNGKey(0), 0x77647721) — the reserved window tag fold ("wdw!"),
# and the full two-level window_bucket_key derivation for epochs 0, 1, 5:
# fold_in(WINDOW_TAG_FOLD, epoch)
WINDOW_TAG_FOLD = [2296611242, 153240566]
WINDOW_KEYS = {0: [1127536114, 704093423],
               1: [1755690605, 2856154744],
               5: [1564771073, 3152420000]}

# fold_in(PRNGKey(0), 0x63736B21) — the RefinementEngine's reserved co-sketch
# tag fold ("csk!"), and the second-level test-matrix keys:
# omega = normal(fold_in(tag fold, 0)), psi = normal(fold_in(tag fold, 1))
COSKETCH_TAG_FOLD = [1946431690, 1695170262]
COSKETCH_OMEGA_KEY = [1132837233, 2203595539]
COSKETCH_PSI_KEY = [3222476339, 429157182]

# fold_in(PRNGKey(0), 0x746E7421) — the reserved tenant tag fold ("tnt!"),
# and the full two-level tenant_key derivation for a str and an int tenant:
# fold_in(TENANT_TAG_FOLD, tenant_id) with tenant_id("acme") = crc32 masked
# to uint31 = 96778814 and tenant_id(7) = 7
TENANT_TAG_FOLD = [2274185980, 3446456051]
TENANT_ACME_KEY = [1560486690, 3089195157]
TENANT_7_KEY = [2609152254, 3911254465]


def _eq(got_key, want):
    np.testing.assert_array_equal(np.asarray(got_key, np.uint32),
                                  np.asarray(want, np.uint32))


def test_base_key_layout(key):
    _eq(key, KEY0)
    _eq(jax.random.split(key, 3), SMPPCA_SPLIT3)
    _eq(jax.random.split(key), SPLIT2)


def test_row_projection_key_tree(key):
    """projection_rows row i == normal(fold_in(key, i))/sqrt(k), with the
    fold_in values frozen bit-for-bit."""
    for i, kd in ROW_KEYS.items():
        _eq(jax.random.fold_in(key, i), kd)
        got = summary_engine.projection_rows(key, jnp.array([i]), 8)[0]
        want = jax.random.normal(jnp.asarray(kd, jnp.uint32),
                                 (8,)) / jnp.sqrt(8.0)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_srht_plan_key_tree(key):
    """srht_plan = (rademacher(sign key), choice(row key)) with the single
    split frozen."""
    signs, rows, dp = summary_engine.srht_plan(key, 48, 16)
    k_sign, k_rows = (jnp.asarray(k, jnp.uint32) for k in SPLIT2)
    np.testing.assert_array_equal(
        np.asarray(signs),
        np.asarray(jax.random.rademacher(k_sign, (48,), dtype=jnp.float32)))
    np.testing.assert_array_equal(
        np.asarray(rows),
        np.asarray(jax.random.choice(k_rows, dp, (16,), replace=False)))


def test_smppca_key_tree(key):
    """smppca == build_summary(k_sketch) + estimate_product(fold_in(
    k_sample, 0)) with every derived key frozen."""
    _eq(jax.random.fold_in(jnp.asarray(SMPPCA_SPLIT3[1], jnp.uint32), 0),
        SMPPCA_EST_KEY)
    _eq(jax.random.split(jnp.asarray(SMPPCA_EST_KEY, jnp.uint32)),
        EST_SPLIT2)
    A = jax.random.normal(key, (96, 10))
    B = jax.random.normal(jax.random.fold_in(key, 1), (96, 8))
    res = core.smppca(key, A, B, r=2, k=16, m=200, T=2)
    summary = summary_engine.build_summary(
        jnp.asarray(SMPPCA_SPLIT3[0], jnp.uint32), A, B, 16)
    manual = estimation_engine.estimate_product(
        jnp.asarray(SMPPCA_EST_KEY, jnp.uint32), summary, 2, m=200, T=2)
    np.testing.assert_array_equal(np.asarray(res.factors.U),
                                  np.asarray(manual.factors.U))
    np.testing.assert_array_equal(np.asarray(res.samples.rows),
                                  np.asarray(manual.samples.rows))


def test_lela_key_tree(key):
    """lela passes the caller key straight to estimate_product (whose single
    split is frozen above): composition equality, no hidden folds."""
    A = jax.random.normal(key, (96, 10))
    B = jax.random.normal(jax.random.fold_in(key, 1), (96, 8))
    got = core.lela(key, A, B, r=2, m=200, T=2)
    manual = estimation_engine.estimate_product(
        key, core.norms_only_summary(A, B), 2, method="lela_waltmin",
        m=200, T=2, exact_pair=(A, B))
    np.testing.assert_array_equal(np.asarray(got.U),
                                  np.asarray(manual.factors.U))


def test_sketch_svd_key_tree(key):
    """sketch_svd == build_summary(split[0]) + direct_svd(split[1]) with the
    split frozen."""
    A = jax.random.normal(key, (96, 10))
    B = jax.random.normal(jax.random.fold_in(key, 1), (96, 8))
    got = core.sketch_svd(key, A, B, r=2, k=16)
    k_sketch, k_pow = (jnp.asarray(k, jnp.uint32) for k in SPLIT2)
    summary = summary_engine.build_summary(k_sketch, A, B, 16)
    manual = estimation_engine.estimate_product(
        k_pow, summary, 2, method="direct_svd")
    np.testing.assert_array_equal(np.asarray(got.U),
                                  np.asarray(manual.factors.U))


def test_sketch_service_key_tree(key):
    """flush_factors derives each request's estimation key as
    fold_in(request key, 1) — frozen and observable through the service."""
    from repro.serve.engine import SketchService
    _eq(jax.random.fold_in(key, 1), SERVICE_EST_KEY)
    A = jax.random.normal(key, (64, 6))
    B = jax.random.normal(jax.random.fold_in(key, 2), (64, 5))
    svc = SketchService(k=8, backend="scan", block=32)
    ticket = svc.submit(key, A, B)
    served = svc.flush_factors(r=2, m=100, T=2)[ticket]
    summary = summary_engine.build_summary(key, A, B, 8, backend="scan",
                                           block=32)
    manual = estimation_engine.estimate_product(
        jnp.asarray(SERVICE_EST_KEY, jnp.uint32), summary, 2, m=100, T=2)
    # the served path is vmapped over the batch, the manual one is not:
    # XLA may fuse the two differently, so they agree to float rounding (a
    # wrong key would change every entry at O(1))
    np.testing.assert_allclose(np.asarray(served.factors.U),
                               np.asarray(manual.factors.U),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_plan_key_tree(key):
    """The plan-compiled path consumes exactly the frozen key tree: the
    smppca/sketch_svd presets and the service layout, executed through a
    PipelineEngine's fused executables, reproduce the stage-by-stage
    compositions built from the golden key literals bit-for-bit."""
    from repro.core import pipeline
    A = jax.random.normal(key, (96, 10))
    B = jax.random.normal(jax.random.fold_in(key, 1), (96, 8))
    eng = pipeline.PipelineEngine()

    # smppca preset: sketch key = split3[0], estimation key = fold(split3[1])
    res = eng.run(pipeline.smppca_plan(r=2, k=16, m=200, T=2), key, A, B)
    summary = summary_engine.build_summary(
        jnp.asarray(SMPPCA_SPLIT3[0], jnp.uint32), A, B, 16)
    manual = estimation_engine.estimate_product(
        jnp.asarray(SMPPCA_EST_KEY, jnp.uint32), summary, 2, m=200, T=2)
    np.testing.assert_array_equal(np.asarray(res.estimate.factors.U),
                                  np.asarray(manual.factors.U))

    # sketch_svd preset: (sketch key, power key) = the single split
    res = eng.run(pipeline.sketch_svd_plan(r=2, k=16), key, A, B)
    k_sketch, k_pow = (jnp.asarray(k, jnp.uint32) for k in SPLIT2)
    summary = summary_engine.build_summary(k_sketch, A, B, 16)
    manual = estimation_engine.estimate_product(
        k_pow, summary, 2, method="direct_svd")
    np.testing.assert_array_equal(np.asarray(res.estimate.factors.U),
                                  np.asarray(manual.factors.U))

    # service layout from a summary (the stream_factors spine): estimation
    # key = fold_in(key, 1), frozen as SERVICE_EST_KEY
    summary = summary_engine.build_summary(key, A, B, 16)
    plan = pipeline.PipelinePlan(
        sketch=pipeline.SketchSpec(k=16),
        estimation=pipeline.EstimationSpec(m=200, T=2),
        rank=pipeline.RankPolicy(r=2), key_layout="service")
    est = eng.run_from_summary(plan, key, summary)
    manual = estimation_engine.estimate_product(
        jnp.asarray(SERVICE_EST_KEY, jnp.uint32), summary, 2, m=200, T=2)
    np.testing.assert_array_equal(np.asarray(est.factors.U),
                                  np.asarray(manual.factors.U))

    # the derivation helper itself is pinned to the same literals
    _eq(pipeline.derive_keys("service", key)[1], SERVICE_EST_KEY)
    _eq(pipeline.derive_keys("smppca", key)[0], SMPPCA_SPLIT3[0])
    _eq(pipeline.derive_keys("smppca", key)[1], SMPPCA_EST_KEY)
    _eq(pipeline.derive_keys("sketch_svd", key)[0], SPLIT2[0])
    _eq(pipeline.derive_keys("sketch_svd", key)[1], SPLIT2[1])
    _eq(pipeline.derive_keys("direct", key)[1], KEY0)


def test_tenant_key_tree(key):
    """The multi-tenant namespacing fold is frozen: tenant_key is the
    reserved two-level ``fold_in(fold_in(key, 0x746E7421), tenant_id)``,
    tenant ids are canonical (ints pass through, strs crc32-masked), and
    ``derive_keys(tenant=...)`` applies the fold BEFORE the layout fan-out
    while ``tenant=None`` leaves every historical derivation untouched."""
    from repro.core import pipeline
    _eq(jax.random.fold_in(key, 0x746E7421), TENANT_TAG_FOLD)
    assert pipeline.tenant_id("acme") == 96778814
    assert pipeline.tenant_id(7) == 7
    _eq(pipeline.tenant_key(key, "acme"), TENANT_ACME_KEY)
    _eq(pipeline.tenant_key(key, 96778814), TENANT_ACME_KEY)   # id == str
    _eq(pipeline.tenant_key(key, 7), TENANT_7_KEY)

    # the fold namespaces BEFORE the layout fan-out: deriving under a tenant
    # == deriving from the folded key, for every layout
    acme = jnp.asarray(TENANT_ACME_KEY, jnp.uint32)
    for layout in ("service", "smppca", "sketch_svd", "direct"):
        got = pipeline.derive_keys(layout, key, tenant="acme")
        want = pipeline.derive_keys(layout, acme)
        _eq(got[0], np.asarray(want[0], np.uint32))
        _eq(got[1], np.asarray(want[1], np.uint32))
    # tenant=None is bit-identical to the pre-tenant derivation
    _eq(pipeline.derive_keys("service", key, tenant=None)[1],
        SERVICE_EST_KEY)

    # batched mode folds each stacked key independently
    stack = jnp.stack([key, jax.random.fold_in(key, 3)])
    got = pipeline.derive_keys("service", stack, batched=True,
                               tenant="acme")[0]
    _eq(got[0], TENANT_ACME_KEY)

    # invalid tenant handles are rejected, not silently hashed
    import pytest
    for bad in (True, 3.5, None, -1, 2 ** 31):
        with pytest.raises((TypeError, ValueError)):
            pipeline.tenant_id(bad)


def test_window_bucket_key_tree(key):
    """The sliding window's per-epoch bucket keys are frozen: the reserved
    two-level ``fold_in(fold_in(key, 0x77647721), epoch)`` fold ("wdw!"),
    and a WindowedSummarizer bucket's carried key and sketch contents are
    exactly those of a plain summarizer initialized at the golden key —
    while the probe test matrix stays the BASE key's (probe blocks only
    merge across buckets against a shared omega)."""
    from repro.core.streaming import (
        StreamingSummarizer, WindowedSummarizer, window_bucket_key)
    _eq(jax.random.fold_in(key, 0x77647721), WINDOW_TAG_FOLD)
    for epoch, kd in WINDOW_KEYS.items():
        _eq(window_bucket_key(key, epoch), kd)

    win = WindowedSummarizer(8, 2, probes=3)
    w = win.init(key, (64, 6, 4))
    A = jax.random.normal(key, (64, 6))
    B = jax.random.normal(jax.random.fold_in(key, 2), (64, 4))
    w = win.slide(w, 4)                       # head: 1 -> 5
    w = win.update(w, A, B, 0)                # rows land in epoch 5's bucket
    bucket = w.buckets[5 % 2]
    _eq(bucket.key, WINDOW_KEYS[5])
    manual = StreamingSummarizer(8, probes=3)
    ref = manual.init(jnp.asarray(WINDOW_KEYS[5], jnp.uint32), (64, 6, 4))
    ref = ref._replace(omega=probe_omega(key, 4, 3))   # the shared base omega
    ref = manual.update(ref, A, B, 0)
    np.testing.assert_array_equal(np.asarray(bucket.A_acc),
                                  np.asarray(ref.A_acc))
    np.testing.assert_array_equal(np.asarray(bucket.probe_acc),
                                  np.asarray(ref.probe_acc))
    np.testing.assert_array_equal(np.asarray(bucket.omega),
                                  np.asarray(probe_omega(key, 4, 3)))


def test_cosketch_key_tree(key):
    """The refinement co-sketch block's reserved two-level fold is frozen
    ("csk!" then sub-index 0/1 for omega/psi), and build_summary's retained
    test matrices are drawn from exactly those keys — so a co-sketch built
    during serving is bit-reproducible from the caller's base key alone."""
    from repro.core.refinement import (
        cosketch_key, cosketch_omega, cosketch_psi, cosketch_width)
    _eq(cosketch_key(key), COSKETCH_TAG_FOLD)
    _eq(jax.random.fold_in(key, 0x63736B21), COSKETCH_TAG_FOLD)
    tag = jnp.asarray(COSKETCH_TAG_FOLD, jnp.uint32)
    _eq(jax.random.fold_in(tag, 0), COSKETCH_OMEGA_KEY)
    _eq(jax.random.fold_in(tag, 1), COSKETCH_PSI_KEY)

    A = jax.random.normal(key, (64, 6))
    B = jax.random.normal(jax.random.fold_in(key, 2), (64, 5))
    s = summary_engine.build_summary(key, A, B, 8, cosketch=3)
    want_omega = jax.random.normal(
        jnp.asarray(COSKETCH_OMEGA_KEY, jnp.uint32), (5, 3))
    want_psi = jax.random.normal(
        jnp.asarray(COSKETCH_PSI_KEY, jnp.uint32), (cosketch_width(3), 6))
    np.testing.assert_array_equal(np.asarray(s.cosketch_omega),
                                  np.asarray(want_omega))
    np.testing.assert_array_equal(np.asarray(s.cosketch_psi),
                                  np.asarray(want_psi))
    np.testing.assert_array_equal(np.asarray(cosketch_omega(key, 5, 3)),
                                  np.asarray(s.cosketch_omega))
    np.testing.assert_array_equal(np.asarray(cosketch_psi(key, 6, 3)),
                                  np.asarray(s.cosketch_psi))


def test_probe_key_tree(key):
    """The ErrorEngine's reserved two-level probe fold is frozen, and
    build_summary's retained probe_omega is drawn from exactly that key."""
    _eq(probe_key(key), PROBE_KEY)
    _eq(jax.random.fold_in(key, 0x70726F62),
        np.asarray([3608120998, 148634447], np.uint32))
    A = jax.random.normal(key, (64, 6))
    B = jax.random.normal(jax.random.fold_in(key, 2), (64, 5))
    s = summary_engine.build_summary(key, A, B, 8, probes=4)
    np.testing.assert_array_equal(
        np.asarray(s.probe_omega),
        np.asarray(jax.random.normal(jnp.asarray(PROBE_KEY, jnp.uint32),
                                     (5, 4))))
    np.testing.assert_array_equal(np.asarray(probe_omega(key, 5, 4)),
                                  np.asarray(s.probe_omega))
