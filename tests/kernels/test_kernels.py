"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode runs
the exact TPU kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # hypothesis is optional; see tests/_hyp.py
    from tests._hyp import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.hadamard import hadamard_matrix


# ---------------------------------------------------------------------------
# sketch_fused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,d,n", [
    (8, 256, 128), (32, 512, 256), (64, 1000, 300),   # unaligned d/n
    (128, 128, 64), (16, 2048, 512), (4, 96, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sketch_fused_sweep(k, d, n, dtype):
    kk = jax.random.PRNGKey(k * 1000 + d + n)
    Pi = jax.random.normal(kk, (k, d), jnp.float32).astype(dtype)
    A = jax.random.normal(jax.random.fold_in(kk, 1), (d, n), jnp.float32).astype(dtype)
    out, norms = ops.sketch_fused(Pi, A)
    out_r, n2_r = ref.sketch_fused_ref(Pi, A)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=tol, atol=tol * np.abs(np.asarray(out_r)).max())
    np.testing.assert_allclose(np.asarray(norms), np.sqrt(np.asarray(n2_r)),
                               rtol=tol)


def test_sketch_fused_block_shape_independence():
    """Different BlockSpec tilings must produce identical results."""
    kk = jax.random.PRNGKey(3)
    Pi = jax.random.normal(kk, (16, 640))
    A = jax.random.normal(jax.random.fold_in(kk, 1), (640, 192))
    o1, n1 = ops.sketch_fused(Pi, A, bn=64, bd=128)
    o2, n2 = ops.sketch_fused(Pi, A, bn=256, bd=512)
    # different tilings reassociate the f32 d-accumulation; with d=640 terms
    # of magnitude O(1) the roundoff floor is a few e-5 absolute
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5,
                               atol=5e-5)
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), rtol=1e-6)


def test_sketch_summary_fused_matches_core():
    """Kernel-backed summary is a valid SketchSummary for the full pipeline."""
    kk = jax.random.PRNGKey(0)
    A = jax.random.normal(kk, (500, 60))
    B = jax.random.normal(jax.random.fold_in(kk, 1), (500, 40))
    s = ops.sketch_summary_fused(kk, A, B, k=32)
    np.testing.assert_allclose(np.asarray(s.norm_A),
                               np.linalg.norm(np.asarray(A), axis=0), rtol=1e-4)
    assert s.A_sketch.shape == (32, 60) and s.B_sketch.shape == (32, 40)


# ---------------------------------------------------------------------------
# sampled_dot
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n1,n2,k,m", [
    (20, 30, 8, 17), (100, 50, 64, 128), (7, 9, 16, 5), (64, 64, 128, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sampled_dot_sweep(n1, n2, k, m, dtype):
    kk = jax.random.PRNGKey(n1 + n2 + k + m)
    As = jax.random.normal(kk, (n1, k), jnp.float32).astype(dtype)
    Bs = jax.random.normal(jax.random.fold_in(kk, 1), (n2, k), jnp.float32).astype(dtype)
    na = jnp.abs(jax.random.normal(jax.random.fold_in(kk, 2), (n1,))) + 0.5
    nb = jnp.abs(jax.random.normal(jax.random.fold_in(kk, 3), (n2,))) + 0.5
    rows = jax.random.randint(jax.random.fold_in(kk, 4), (m,), 0, n1)
    cols = jax.random.randint(jax.random.fold_in(kk, 5), (m,), 0, n2)
    got = ops.sampled_rescaled_dot(As, Bs, na, nb, rows, cols)
    want = ref.sampled_rescaled_dot_ref(As, Bs, na, nb, rows, cols)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol,
                               atol=tol)


def test_sampled_dot_duplicate_indices():
    kk = jax.random.PRNGKey(0)
    As = jax.random.normal(kk, (10, 8))
    Bs = jax.random.normal(jax.random.fold_in(kk, 1), (10, 8))
    ones = jnp.ones((10,))
    rows = jnp.array([3, 3, 3, 0], jnp.int32)
    cols = jnp.array([5, 5, 2, 0], jnp.int32)
    got = ops.sampled_rescaled_dot(As, Bs, ones, ones, rows, cols)
    want = ref.sampled_rescaled_dot_ref(As, Bs, ones, ones, rows, cols)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_sampled_dot_empty_sample_set():
    """m = 0 (an empty Omega) returns an empty result instead of tripping a
    zero-size grid slice; kernel and oracle agree on the shape."""
    kk = jax.random.PRNGKey(0)
    As = jax.random.normal(kk, (5, 8))
    Bs = jax.random.normal(jax.random.fold_in(kk, 1), (4, 8))
    na, nb = jnp.ones((5,)), jnp.ones((4,))
    empty = jnp.zeros((0,), jnp.int32)
    got = ops.sampled_rescaled_dot(As, Bs, na, nb, empty, empty)
    want = ref.sampled_rescaled_dot_ref(As, Bs, na, nb, empty, empty)
    assert got.shape == want.shape == (0,)
    assert got.dtype == jnp.float32


def test_sampled_dot_more_samples_than_entries():
    """m > n1 * n2: the sample necessarily repeats entries — every duplicate
    gathers the identical sketch rows, so duplicates agree bit-for-bit
    among themselves (same f32 ops per grid step), and the kernel matches
    the oracle to f32 rounding (the oracle's reductions run vectorized over
    all samples, which XLA may fuse and order differently)."""
    kk = jax.random.PRNGKey(7)
    As = jax.random.normal(kk, (5, 8))
    Bs = jax.random.normal(jax.random.fold_in(kk, 1), (4, 8))
    na = jnp.abs(jax.random.normal(jax.random.fold_in(kk, 2), (5,))) + 0.5
    nb = jnp.abs(jax.random.normal(jax.random.fold_in(kk, 3), (4,))) + 0.5
    m = 3 * 5 * 4                       # 3x the number of distinct entries
    rows = jax.random.randint(jax.random.fold_in(kk, 4), (m,), 0, 5)
    cols = jax.random.randint(jax.random.fold_in(kk, 5), (m,), 0, 4)
    got = ops.sampled_rescaled_dot(As, Bs, na, nb, rows, cols)
    want = ref.sampled_rescaled_dot_ref(As, Bs, na, nb, rows, cols)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # duplicates really occurred and agree among themselves
    pairs = np.stack([np.asarray(rows), np.asarray(cols)], 1)
    _, inv = np.unique(pairs, axis=0, return_inverse=True)
    for g in range(inv.max() + 1):
        vals = np.asarray(got)[inv == g]
        assert np.all(vals == vals[0])


# ---------------------------------------------------------------------------
# hadamard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,b,n", [
    (128, 128, 64),     # a == 1, single stage
    (256, 64, 100),     # unaligned n
    (512, 128, 256),
    (1024, 32, 96),
])
def test_blocked_fwht_sweep(d, b, n):
    kk = jax.random.PRNGKey(d + b + n)
    X = jax.random.normal(kk, (d, n))
    signs = jax.random.rademacher(jax.random.fold_in(kk, 1), (d,),
                                  dtype=jnp.float32)
    got = ops.blocked_fwht(X, signs, b=b)
    want = ref.blocked_fwht_ref(X, signs)
    scale = np.abs(np.asarray(want)).max()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4 * scale)


def test_fwht_butterfly_equals_sylvester_matrix():
    """Cross-check both references against the explicit H matrix."""
    d = 64
    kk = jax.random.PRNGKey(0)
    X = jax.random.normal(kk, (d, 5))
    H = np.asarray(hadamard_matrix(d))
    want = H @ np.asarray(X)
    got = np.asarray(ref.blocked_fwht_ref(X, jnp.ones((d,))))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_srht_kernel_preserves_geometry():
    """Kernel-backed SRHT is a valid subspace embedding (norm preservation)."""
    kk = jax.random.PRNGKey(0)
    X = jax.random.normal(kk, (777, 40))     # non-power-of-two d
    S = ops.srht_sketch_kernel(kk, X, k=512)
    norms_in = np.linalg.norm(np.asarray(X), axis=0)
    norms_out = np.linalg.norm(np.asarray(S), axis=0)
    assert np.mean(np.abs(norms_out - norms_in) / norms_in) < 0.1


@settings(deadline=None, max_examples=8)
@given(logd=st.integers(5, 9), seed=st.integers(0, 2**31 - 1))
def test_property_fwht_parseval(logd, seed):
    """H/sqrt(d) is orthogonal: the kernel must preserve Frobenius norm."""
    d = 2 ** logd
    kk = jax.random.PRNGKey(seed)
    X = jax.random.normal(kk, (d, 3))
    out = ops.blocked_fwht(X, jnp.ones((d,)), b=min(128, d)) / np.sqrt(d)
    np.testing.assert_allclose(float(jnp.linalg.norm(out)),
                               float(jnp.linalg.norm(X)), rtol=1e-4)


def test_hadamard_matrix_non_pow2_raises_named_valueerror():
    """hadamard_matrix rejects non-power-of-two sizes with a ValueError
    naming n, never a strippable assert."""
    with pytest.raises(ValueError, match="n=12"):
        hadamard_matrix(12)
    with pytest.raises(ValueError, match="power of two"):
        hadamard_matrix(0)
    assert hadamard_matrix(8).shape == (8, 8)
