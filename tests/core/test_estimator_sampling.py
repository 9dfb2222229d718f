"""Step-2 tests: rescaled JL estimator (Eq 2) and biased sampling (Eq 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # hypothesis is optional; see tests/_hyp.py
    from tests._hyp import given, settings, strategies as st

from repro import core
from tests.conftest import cone_pair, figure_key, planted_pair


# ---------------------------------------------------------------------------
# Rescaled JL estimator
# ---------------------------------------------------------------------------

def test_fig2a_rescaled_beats_plain_jl(key):
    """Paper Fig 2(a): on unit-norm vector pairs with varying angles, the
    rescaled estimator has lower MSE than the plain JL dot product
    (paper: 0.053 vs 0.129 at d=1000, k=10)."""
    d, k, npairs = 1000, 10, 400
    kx, kt, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (d, npairs))
    x = x / jnp.linalg.norm(x, axis=0)
    # y = x + t with E||t|| ~ 0.6 (paper Fig 2a construction: moderate angles,
    # where Eq 2's (1 - cos^2)^2/k beats plain JL's (1 + cos^2)/k decisively;
    # without the 1/sqrt(d) the angles are ~90 deg and the gap is seed noise)
    t = jax.random.normal(kt, (d, npairs)) * 0.6 / jnp.sqrt(d)
    y = x + t
    y = y / jnp.linalg.norm(y, axis=0)
    true = jnp.sum(x * y, axis=0)
    s = core.sketch_summary(ks, x, y, k=k)
    idx = jnp.arange(npairs)
    est_resc = core.rescaled_entries(s, idx, idx)
    est_plain = core.plain_jl_entries(s, idx, idx)
    mse_resc = float(jnp.mean((est_resc - true) ** 2))
    mse_plain = float(jnp.mean((est_plain - true) ** 2))
    assert mse_resc < mse_plain, (mse_resc, mse_plain)


@pytest.mark.parametrize("theta", [0.2, 0.8, 2.0])
def test_fig2b_rescaled_matrix_beats_plain_sketch_product(theta):
    """Paper Fig 2(b): for columns drawn from a cone, the rescaled estimate
    M~ has a smaller spectral error than the plain sketch product A~^T B~,
    the more so the narrower the cone (CPU: ratios 63, 8.6 and 1.15)."""
    key = figure_key("fig2b_cone")
    d, n, k = 1000, 120, 32
    A, B = cone_pair(jax.random.fold_in(key, int(theta * 10)), d, n, theta)
    M = A.T @ B
    s = core.build_summary(key, A, B, k)
    e_plain = jnp.linalg.norm(M - s.A_sketch.T @ s.B_sketch, ord=2)
    e_resc = jnp.linalg.norm(M - core.rescaled_matrix(s), ord=2)
    assert float(e_plain) > float(e_resc), (float(e_plain), float(e_resc))


def test_rescaled_exact_when_colinear(key):
    """Extreme case of Fig 2(a): cos theta = 1 -> rescaled JL is *exact*."""
    d, n, k = 300, 8, 4
    kx, ks = jax.random.split(key)
    x = jax.random.normal(kx, (d, n))
    scales = jnp.arange(1.0, n + 1.0)
    A = x
    B = x * scales[None, :]          # B_j parallel to A_j
    s = core.sketch_summary(ks, A, B, k=k)
    idx = jnp.arange(n)
    est = core.rescaled_entries(s, idx, idx)
    true = jnp.sum(A * B, axis=0)
    np.testing.assert_allclose(np.asarray(est), np.asarray(true), rtol=1e-3)


def test_rescaled_matrix_matches_entries(key):
    A, B = planted_pair(key, 200, 15, corr=0.3)
    s = core.sketch_summary(key, A, B, k=64)
    M = core.rescaled_matrix(s)
    ii, jj = jnp.meshgrid(jnp.arange(15), jnp.arange(15), indexing="ij")
    entries = core.rescaled_entries(s, ii.reshape(-1), jj.reshape(-1))
    np.testing.assert_allclose(np.asarray(M).reshape(-1), np.asarray(entries),
                               rtol=1e-4, atol=1e-5)


def test_lemma_b6_entrywise_bound(key):
    """Lemma B.6: |M~_ij - A_i^T B_j| <= eps ||A_i|| ||B_j|| whp, eps ~
    sqrt(log n / k). Checked at 3x the nominal eps."""
    d, n, k = 2000, 40, 512
    A, B = planted_pair(key, d, n, corr=0.5)
    s = core.sketch_summary(key, A, B, k=k)
    M = np.asarray(core.rescaled_matrix(s))
    exact = np.asarray(A.T @ B)
    scale = np.asarray(s.norm_A)[:, None] * np.asarray(s.norm_B)[None, :]
    eps = 3.0 * np.sqrt(np.log(2 * n) / k)
    assert np.all(np.abs(M - exact) <= eps * scale)


# ---------------------------------------------------------------------------
# Eq-(1) sampling
# ---------------------------------------------------------------------------

def test_q_probabilities_expected_count(key):
    norm_A = jnp.abs(jax.random.normal(key, (50,))) + 0.1
    norm_B = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (70,))) + 0.1
    m = 500
    q = core.q_probabilities(norm_A, norm_B, m)
    # sum q_ij == m when no entry saturates (Eq 1 normalization)
    qr = m * (norm_A[:, None] ** 2 / (2 * 70 * jnp.sum(norm_A ** 2))
              + norm_B[None, :] ** 2 / (2 * 50 * jnp.sum(norm_B ** 2)))
    assert abs(float(jnp.sum(qr)) - m) < 1e-3 * m
    assert float(jnp.max(q)) <= 1.0


def test_sampler_marginals_match_eq1(key):
    """Empirical row-marginals of the factored sampler match the Eq-(1)
    mixture: P(row=i) = 1/2 ||A_i||^2/||A||_F^2 + 1/(2 n1)."""
    n1, n2, m = 30, 20, 200_000
    norm_A = jnp.linspace(0.2, 3.0, n1)
    norm_B = jnp.linspace(1.0, 2.0, n2)
    ss = core.sample_entries(key, norm_A, norm_B, m)
    counts = np.bincount(np.asarray(ss.rows), minlength=n1) / m
    expect = 0.5 * np.asarray(norm_A ** 2 / jnp.sum(norm_A ** 2)) + 0.5 / n1
    np.testing.assert_allclose(counts, expect, atol=0.01)


def test_sampler_qhat_evaluation(key):
    norm_A = jnp.ones((10,))
    norm_B = jnp.ones((10,))
    ss = core.sample_entries(key, norm_A, norm_B, 50)
    # uniform norms: q_ij = m (1/(2*100) + 1/(2*100)) = m/100
    np.testing.assert_allclose(np.asarray(ss.q_hat), 0.5, rtol=1e-5)


def test_binomial_sampler_agrees_with_q(key):
    n = 40
    norm_A = jnp.linspace(0.5, 2.0, n)
    norm_B = jnp.linspace(0.5, 2.0, n)
    m = 300
    ss = core.sample_entries_binomial(key, norm_A, norm_B, m)
    n_sampled = int(np.asarray(ss.mask).sum())
    assert 0.5 * m < n_sampled < 2.0 * m


@settings(deadline=None, max_examples=10)
@given(n1=st.integers(3, 30), n2=st.integers(3, 30),
       m=st.integers(10, 400), seed=st.integers(0, 2**31 - 1))
def test_property_sampler_static_shapes_and_ranges(n1, n2, m, seed):
    kk = jax.random.PRNGKey(seed)
    norm_A = jnp.abs(jax.random.normal(kk, (n1,))) + 0.01
    norm_B = jnp.abs(jax.random.normal(jax.random.fold_in(kk, 1), (n2,))) + 0.01
    ss = core.sample_entries(kk, norm_A, norm_B, m)
    assert ss.rows.shape == (m,) and ss.cols.shape == (m,)
    assert int(ss.rows.min()) >= 0 and int(ss.rows.max()) < n1
    assert int(ss.cols.min()) >= 0 and int(ss.cols.max()) < n2
    q = np.asarray(ss.q_hat)
    assert np.all(q > 0) and np.all(q <= 1.0)


# ---------------------------------------------------------------------------
# Zero-norm / degenerate-CDF hardening
# ---------------------------------------------------------------------------

def test_zero_matrix_raises_named_valueerror(key):
    """An all-zero factor makes Eq. (1) a 0/0: both samplers refuse eagerly
    with a ValueError naming WHICH factor is degenerate."""
    import pytest
    zeros = jnp.zeros((8,))
    ones = jnp.ones((8,))
    with pytest.raises(ValueError, match="columns of A"):
        core.sample_entries(key, zeros, ones, 20)
    with pytest.raises(ValueError, match="columns of B"):
        core.sample_entries(key, ones, zeros, 20)
    with pytest.raises(ValueError, match="columns of A"):
        core.sample_entries_binomial(key, zeros, ones, 20)
    with pytest.raises(ValueError, match="columns of B"):
        core.sample_entries_binomial(key, ones, jnp.full((8,), jnp.nan), 20)


def test_zero_matrix_raises_through_estimate_product(key):
    """The guard fires end-to-end: estimate_product on a summary of an
    all-zero A raises the named ValueError instead of returning NaN
    factors — for both sampling-based methods."""
    import pytest
    from repro.core import estimation_engine
    from repro.core.summary_engine import build_summary, norms_only_summary
    A = jnp.zeros((64, 6))
    B = jax.random.normal(key, (64, 5))
    summary = build_summary(key, A, B, 8)
    with pytest.raises(ValueError, match="columns of A"):
        estimation_engine.estimate_product(key, summary, 2, m=50, T=2)
    with pytest.raises(ValueError, match="columns of A"):
        estimation_engine.estimate_product(
            key, norms_only_summary(A, B), 2, method="lela_waltmin",
            m=50, T=2, exact_pair=(A, B))


def test_zero_columns_fall_through_uniform_branch(key):
    """Zero-norm *columns* (rows of A^T B) are fine: the Eq. (1) mixture's
    uniform term keeps every q_ij > 0, the sampler stays in range, and
    estimate_product completes with finite factors end-to-end."""
    from repro.core import estimation_engine
    from repro.core.summary_engine import build_summary
    A = jax.random.normal(key, (64, 6)).at[:, :2].set(0.0)
    B = jax.random.normal(jax.random.fold_in(key, 1), (64, 5))
    norm_A = jnp.linalg.norm(A, axis=0)
    ss = core.sample_entries(key, norm_A, jnp.linalg.norm(B, axis=0), 60)
    q = np.asarray(ss.q_hat)
    assert np.all(q > 0) and np.all(np.isfinite(q))
    summary = build_summary(key, A, B, 16)
    est = estimation_engine.estimate_product(key, summary, 2, m=60, T=2)
    assert np.all(np.isfinite(np.asarray(est.factors.U)))
    assert np.all(np.isfinite(np.asarray(est.factors.V)))
