"""Step-3 + end-to-end tests: WAltMin completion and Algorithm 1."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # hypothesis is optional; see tests/_hyp.py
    from tests._hyp import given, settings, strategies as st

from repro import core
from repro.core.types import SampleSet
from repro.core.waltmin import waltmin
from tests.conftest import cone_pair, figure_key, planted_pair


def _full_sample(n1, n2):
    ii, jj = jnp.meshgrid(jnp.arange(n1), jnp.arange(n2), indexing="ij")
    return SampleSet(ii.reshape(-1).astype(jnp.int32),
                     jj.reshape(-1).astype(jnp.int32),
                     jnp.ones(n1 * n2), jnp.ones(n1 * n2, bool))


def test_waltmin_exact_rank_r_full_observation(key):
    n, r = 80, 4
    kU, kV = jax.random.split(key)
    M = jax.random.normal(kU, (n, r)) @ jax.random.normal(kV, (n, r)).T
    f = waltmin(key, _full_sample(n, n), M.reshape(-1), n, n, r, 4,
                use_splits=False)
    err = float(jnp.linalg.norm(M - f.U @ f.V.T) / jnp.linalg.norm(M))
    assert err < 5e-4, err


def test_waltmin_exact_rank_r_subsampled(key):
    """Exact rank-r matrix from ~35% of uniformly sampled entries."""
    n, r = 100, 3
    kU, kV, ks = jax.random.split(key, 3)
    M = jax.random.normal(kU, (n, r)) @ jax.random.normal(kV, (n, r)).T
    m = int(0.35 * n * n)
    rows = jax.random.randint(ks, (m,), 0, n).astype(jnp.int32)
    cols = jax.random.randint(jax.random.fold_in(ks, 1), (m,), 0, n).astype(jnp.int32)
    q = jnp.full((m,), 0.35)
    ss = SampleSet(rows, cols, q, jnp.ones(m, bool))
    vals = M[rows, cols]
    f = waltmin(key, ss, vals, n, n, r, 10, use_splits=False)
    err = float(jnp.linalg.norm(M - f.U @ f.V.T) / jnp.linalg.norm(M))
    assert err < 1e-2, err


def test_waltmin_splits_mode_bounded(key):
    """Alg-2 sample splitting at small scale is out of its Eq-(5) regime; we
    assert the damped solver stays bounded (no NaN/inf blowup) and T<=2 works."""
    n, r = 100, 3
    kU, kV = jax.random.split(key)
    M = jax.random.normal(kU, (n, r)) @ jax.random.normal(kV, (n, r)).T
    f = waltmin(key, _full_sample(n, n), M.reshape(-1), n, n, r, 2,
                use_splits=True)
    rel = float(jnp.linalg.norm(M - f.U @ f.V.T) / jnp.linalg.norm(M))
    assert np.isfinite(rel) and rel < 0.5, rel


def test_coo_topr_svd_matches_dense(key):
    n1, n2, r = 60, 50, 5
    M = jax.random.normal(key, (n1, n2))
    ii, jj = jnp.meshgrid(jnp.arange(n1), jnp.arange(n2), indexing="ij")
    U, s, V = core.coo_topr_svd(key, ii.reshape(-1), jj.reshape(-1),
                                M.reshape(-1), n1, n2, r)
    s_true = jnp.linalg.svd(M, compute_uv=False)[:r]
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_true), rtol=1e-3)


# ---------------------------------------------------------------------------
# End-to-end SMP-PCA and the paper's figure claims (docs/paper_map.md)
# ---------------------------------------------------------------------------

def _m(n, r):
    return int(10 * n * r * np.log(n))


def test_smppca_recovers_correlated_product(key):
    d, n, r = 2000, 200, 5
    A, B = planted_pair(key, d, n, corr=0.3)
    res = core.smppca(key, A, B, r=r, k=512, m=_m(n, r), T=8)
    err, opt = core.spectral_error_vs_optimal(A, B, r, res.factors)
    assert float(err) < 3.0 * float(opt) + 0.05, (float(err), float(opt))


def test_smppca_error_decreases_with_k(key):
    d, n, r = 1500, 150, 5
    A, B = planted_pair(key, d, n, corr=0.3)
    errs = []
    for k in [32, 128, 1024]:
        res = core.smppca(key, A, B, r=r, k=k, m=_m(n, r), T=8)
        e, _ = core.spectral_error_vs_optimal(A, B, r, res.factors)
        errs.append(float(e))
    assert errs[2] < errs[0], errs  # Thm 3.1: eta ~ 1/sqrt(k)


def test_smppca_beats_sketch_svd(key):
    """The paper's headline comparison (Figs 2b, 3b, 4b)."""
    d, n, r = 2000, 150, 5
    A, B = planted_pair(key, d, n, corr=0.2)  # narrow cone
    k = 128
    res = core.smppca(key, A, B, r=r, k=k, m=_m(n, r), T=8)
    e_smp, _ = core.spectral_error_vs_optimal(A, B, r, res.factors)
    sf = core.sketch_svd(key, A, B, r=r, k=k)
    e_svd, _ = core.spectral_error_vs_optimal(A, B, r, sf)
    assert float(e_smp) < float(e_svd), (float(e_smp), float(e_svd))


def test_lela_approaches_optimal(key):
    d, n, r = 1500, 150, 5
    A, B = planted_pair(key, d, n)
    f = core.lela(key, A, B, r=r, m=_m(n, r), T=8)
    err, opt = core.spectral_error_vs_optimal(A, B, r, f)
    assert float(err) < 1.5 * float(opt) + 0.02


def test_pca_special_case_a_equals_b(key):
    """Remark 3: A=B gives single-pass PCA of A^T A."""
    d, n, r = 1500, 100, 4
    A, _ = planted_pair(key, d, n)
    res = core.smppca(key, A, A, r=r, k=768, m=_m(n, r), T=8)
    err, opt = core.spectral_error_vs_optimal(A, A, r, res.factors)
    assert float(err) < 3.0 * float(opt) + 0.05


def test_product_of_pcas_fails_on_orthogonal_subspaces(key):
    """Fig 4(c): A_r^T B_r is a poor approximation when top subspaces of A
    and B are orthogonal, while SMP-PCA is not."""
    d, n, r = 600, 60, 3
    kq, kn = jax.random.split(key)
    # Q1 (A's top), Q2 (B's top), Qs (shared lower directions), all orthogonal
    Q, _ = jnp.linalg.qr(jax.random.normal(kq, (d, 3 * r)))
    Q1, Q2, Qs = Q[:, :r], Q[:, r:2 * r], Q[:, 2 * r:]
    CA = jax.random.normal(jax.random.fold_in(key, 1), (r, n))
    CB = jax.random.normal(jax.random.fold_in(key, 2), (r, n))
    SA = jax.random.normal(jax.random.fold_in(key, 3), (r, n))
    SB = jax.random.normal(jax.random.fold_in(key, 4), (r, n))
    noise = 0.02 * jax.random.normal(kn, (d, 2 * n))
    A = 3.0 * Q1 @ CA + Qs @ SA + noise[:, :n]
    B = 3.0 * Q2 @ CB + Qs @ SB + noise[:, n:]
    # per-matrix top-r spaces are Q1 vs Q2 (orthogonal) -> A_r^T B_r ~ 0,
    # while A^T B ~ SA^T SB (rank r) carried by the *shared lower* directions
    f_pp = core.product_of_pcas(key, A, B, r)
    e_pp, _ = core.spectral_error_vs_optimal(A, B, r, f_pp)
    res = core.smppca(key, A, B, r=r, k=512, m=_m(n, r), T=8)
    e_smp, _ = core.spectral_error_vs_optimal(A, B, r, res.factors)
    assert float(e_pp) > 0.5
    assert float(e_smp) < float(e_pp)


@functools.lru_cache(maxsize=None)
def _fig4a_lela_error(mult):
    """LELA's spectral error on the Fig 4(a) pair with m = mult * n r log n."""
    key = figure_key("fig4a_phase")
    d, n, r = 1000, 400, 3
    kU, kV = jax.random.split(key)
    A = jax.random.normal(kU, (d, n))
    B = (A @ jax.random.normal(kV, (n, r)) @ jax.random.normal(
        jax.random.fold_in(kV, 1), (r, n)) / n
         + 0.01 * jax.random.normal(jax.random.fold_in(kV, 2), (d, n)))
    m = int(mult * n * r * math.log(n))
    f = core.lela(key, A, B, r=r, m=m, T=8)
    return float(core.spectral_error(A, B, f))


@pytest.mark.parametrize("mult", [1.0, 4.0])
def test_fig4a_lela_phase_transition_at_n_r_log_n(mult):
    """Paper Fig 4(a): LELA's error drops sharply once m reaches
    Theta(n r log n): at half that it is at least ten times the error at
    mult * n r log n (CPU: 1.68 against 0.041 and 0.015)."""
    assert _fig4a_lela_error(0.5) >= 10 * _fig4a_lela_error(mult), (
        _fig4a_lela_error(0.5), _fig4a_lela_error(mult))


@pytest.mark.parametrize("theta", [0.2, 1.0])
def test_fig4b_smppca_beats_sketch_svd_on_cone(theta):
    """Paper Fig 4(b): through sampling and WAltMin, SMP-PCA's error on a
    cone pair is below SVD(A~^T B~)'s at the same k (CPU: ratios 45 and
    4.9)."""
    key = figure_key("fig4b_cone_full")
    d, n, r, k = 1000, 150, 3, 64
    m = int(10 * n * r * math.log(n))
    A, B = cone_pair(jax.random.fold_in(key, int(theta * 10)), d, n, theta)
    res = core.smppca(key, A, B, r=r, k=k, m=m, T=6)
    e_smp = float(core.spectral_error(A, B, res.factors))
    sf = core.sketch_svd(key, A, B, r=r, k=k)
    e_svd = float(core.spectral_error(A, B, sf))
    assert e_smp < e_svd, (e_smp, e_svd)


def test_smppca_streaming_summary_entry_point(key):
    """smppca_from_summary == smppca when fed the same summary."""
    d, n, r = 800, 80, 3
    A, B = planted_pair(key, d, n, corr=0.3)
    m = _m(n, r)
    res1 = core.smppca(key, A, B, r=r, k=256, m=m, T=6)
    err1, _ = core.spectral_error_vs_optimal(A, B, r, res1.factors)
    assert float(err1) < 1.0


@settings(deadline=None, max_examples=6)
@given(n=st.sampled_from([40, 70]), r=st.integers(1, 4),
       seed=st.integers(0, 2**31 - 1))
def test_property_waltmin_completes_exact_lowrank(n, r, seed):
    """Property: any exact rank-r matrix is completed from full observation."""
    kk = jax.random.PRNGKey(seed)
    kU, kV = jax.random.split(kk)
    M = jax.random.normal(kU, (n, r)) @ jax.random.normal(kV, (n, r)).T
    ii, jj = jnp.meshgrid(jnp.arange(n), jnp.arange(n), indexing="ij")
    ss = SampleSet(ii.reshape(-1).astype(jnp.int32),
                   jj.reshape(-1).astype(jnp.int32),
                   jnp.ones(n * n), jnp.ones(n * n, bool))
    f = waltmin(kk, ss, M.reshape(-1), n, n, r, 3, use_splits=False)
    err = float(jnp.linalg.norm(M - f.U @ f.V.T) / jnp.linalg.norm(M))
    assert err < 1e-3, err


@settings(deadline=None, max_examples=6)
@given(seed=st.integers(0, 2**31 - 1))
def test_property_masked_padding_is_ignored(seed):
    """Padding entries (mask=False) must not affect the completion."""
    kk = jax.random.PRNGKey(seed)
    n, r = 50, 2
    kU, kV = jax.random.split(kk)
    M = jax.random.normal(kU, (n, r)) @ jax.random.normal(kV, (n, r)).T
    ii, jj = jnp.meshgrid(jnp.arange(n), jnp.arange(n), indexing="ij")
    rows = ii.reshape(-1).astype(jnp.int32)
    cols = jj.reshape(-1).astype(jnp.int32)
    vals = M.reshape(-1)
    ss1 = SampleSet(rows, cols, jnp.ones(n * n), jnp.ones(n * n, bool))
    # append garbage padding
    pad = 64
    ss2 = SampleSet(jnp.concatenate([rows, jnp.zeros(pad, jnp.int32)]),
                    jnp.concatenate([cols, jnp.zeros(pad, jnp.int32)]),
                    jnp.concatenate([jnp.ones(n * n), jnp.full((pad,), 0.5)]),
                    jnp.concatenate([jnp.ones(n * n, bool), jnp.zeros(pad, bool)]))
    vals2 = jnp.concatenate([vals, jnp.full((pad,), 1e6)])
    f1 = waltmin(kk, ss1, vals, n, n, r, 3, use_splits=False)
    f2 = waltmin(kk, ss2, vals2, n, n, r, 3, use_splits=False)
    np.testing.assert_allclose(np.asarray(f1.U @ f1.V.T),
                               np.asarray(f2.U @ f2.V.T), rtol=1e-3, atol=1e-3)
