"""SummaryEngine tests: backend-parity matrix, batched (vmapped) mode,
precision policy, identity-product path, and the serving front-end.

The engine's contract: identical (key, global_row_index) randomness across
backends, so for a fixed key every backend produces the same summary up to
float reassociation ('rows' shares the reference's exact contraction and is
bit-identical; scan/pallas/distributed reassociate the d-accumulation).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import summary_engine as se
from tests.conftest import gaussian_pair, planted_pair


def _pair(key, d=300, n1=24, n2=18):
    return gaussian_pair(key, d, n1, n2)


def _assert_summary_close(got, want, rtol=2e-4, atol_scale=1e-5):
    for name in ("A_sketch", "B_sketch", "norm_A", "norm_B"):
        g, w = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        np.testing.assert_allclose(
            g, w, rtol=rtol, atol=atol_scale * max(np.abs(w).max(), 1.0),
            err_msg=name)


# ---------------------------------------------------------------------------
# Parity matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["gaussian", "srht"])
@pytest.mark.parametrize("backend", ["scan", "rows", "pallas"])
def test_backend_parity_vs_reference(key, method, backend):
    """Every backend x method cell agrees with the reference backend."""
    A, B = _pair(key)                       # d=300: exercises padding paths
    ref = se.build_summary(key, A, B, 32, method=method, backend="reference")
    got = se.build_summary(key, A, B, 32, method=method, backend=backend,
                           block=128)
    if backend == "rows":                   # same contraction -> bit-identical
        for name in ("A_sketch", "B_sketch", "norm_A", "norm_B"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(ref, name)), err_msg=name)
    else:
        _assert_summary_close(got, ref)


@pytest.mark.parametrize("precision", [None, "bf16"])
@pytest.mark.parametrize("method", ["gaussian", "srht"])
def test_sketch_pair_matches_two_sketch_dots(key, method, precision):
    """Pinning one block's projection changes no bit of either sketch."""
    A, B = _pair(key, d=256)
    gids = 512 + jnp.arange(256)
    plan = se.srht_plan(key, 1024, 32)[:2] if method == "srht" else None

    def _projection(key):
        return se.projection_rows(key, gids, 32, method=method, plan=plan)

    pair = jax.jit(lambda key, A, B: se._sketch_pair(
        _projection(key), A, B, precision))(key, A, B)
    dots = jax.jit(lambda key, A, B: (
        se._sketch_dot(_projection(key), A, precision),
        se._sketch_dot(_projection(key), B, precision)))(key, A, B)
    for got, want, name in zip(pair, dots, ("A", "B")):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)


@pytest.mark.dist
def test_distributed_backend_parity():
    """2-shard CPU mesh vs reference, both methods (subprocess: the main
    pytest process must keep the single real CPU device)."""
    from tests.dist.helpers import run_with_devices
    out = run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.core import summary_engine as se
    mesh = Mesh(np.array(jax.devices()), ("shard",))
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (256, 20))
    B = jax.random.normal(jax.random.fold_in(key, 1), (256, 14))
    for method in ("gaussian", "srht"):
        ref = se.build_summary(key, A, B, 32, method=method,
                               backend="reference")
        got = se.build_summary(key, A, B, 32, method=method,
                               backend="distributed", mesh=mesh, axis="shard")
        for name in ("A_sketch", "B_sketch", "norm_A", "norm_B"):
            g = np.asarray(getattr(got, name))
            w = np.asarray(getattr(ref, name))
            np.testing.assert_allclose(
                g, w, rtol=2e-4, atol=1e-5 * max(np.abs(w).max(), 1.0),
                err_msg=f"{method}/{name}")
    print("DIST_PARITY_OK")
    """, n_devices=2)
    assert "DIST_PARITY_OK" in out


def test_unknown_backend_and_method_raise(key):
    A, B = _pair(key, d=64, n1=4, n2=4)
    with pytest.raises(ValueError, match="backend"):
        se.build_summary(key, A, B, 8, backend="nope")
    with pytest.raises(ValueError, match="method"):
        se.build_summary(key, A, B, 8, method="nope")
    assert set(se.backends()) >= {"reference", "scan", "rows", "pallas",
                                  "distributed"}


def test_srht_is_a_subspace_embedding_on_every_backend(key):
    """Statistical sanity on top of parity: srht preserves column norms."""
    A, B = planted_pair(key, 500, 40, corr=0.5)
    for backend in ("reference", "scan", "pallas"):
        s = se.build_summary(key, A, B, 256, method="srht", backend=backend)
        rel = np.asarray(
            jnp.abs(jnp.linalg.norm(s.A_sketch, axis=0) - s.norm_A)
            / s.norm_A)
        assert rel.mean() < 0.15, backend


# ---------------------------------------------------------------------------
# Batched (vmapped) mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "scan", "pallas"])
def test_batched_matches_looped(key, backend):
    """One vmapped dispatch over a (L, d, n) stack == L single dispatches."""
    L = 3
    A = jax.random.normal(key, (L, 128, 12))
    B = jax.random.normal(jax.random.fold_in(key, 1), (L, 128, 9))
    batched = se.build_summary(key, A, B, 16, backend=backend, block=64)
    keys = jax.random.split(key, L)
    for i in range(L):
        single = se.build_summary(keys[i], A[i], B[i], 16, backend=backend,
                                  block=64)
        _assert_summary_close(
            jax.tree.map(lambda x: x[i], batched), single, rtol=1e-5)


def test_batched_accepts_key_stack(key):
    """An explicit (L, 2) key stack is used verbatim (per-request keys)."""
    L = 2
    A = jax.random.normal(key, (L, 64, 6))
    B = jax.random.normal(jax.random.fold_in(key, 1), (L, 64, 5))
    keys = jax.random.split(jax.random.fold_in(key, 7), L)
    batched = se.build_summary(keys, A, B, 8, backend="scan", block=32)
    single = se.build_summary(keys[1], A[1], B[1], 8, backend="scan",
                              block=32)
    _assert_summary_close(
        jax.tree.map(lambda x: x[1], batched), single, rtol=1e-5)


def test_sketch_service_buckets_and_matches(key):
    """The serving front-end returns per-request results identical to solo
    dispatches, across mixed shape buckets."""
    from repro.serve.engine import SketchService
    svc = SketchService(k=8, backend="scan", block=32)
    reqs = []
    for i, (d, n1, n2) in enumerate([(64, 6, 5), (96, 4, 7), (64, 6, 5)]):
        kk = jax.random.fold_in(key, i)
        A, B = _pair(kk, d, n1, n2)
        reqs.append((svc.submit(kk, A, B), kk, A, B))
    assert svc.pending == 3
    out = svc.flush()
    assert svc.pending == 0
    for ticket, kk, A, B in reqs:
        solo = se.build_summary(kk, A, B, 8, backend="scan", block=32)
        _assert_summary_close(out[ticket], solo, rtol=1e-5)


# ---------------------------------------------------------------------------
# Precision policy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "scan", "pallas"])
def test_bf16_precision_policy(key, backend):
    """bf16-in/f32-accumulate: outputs stay f32 and track the f32 result to
    bf16 input-rounding accuracy."""
    A, B = _pair(key, d=256, n1=16, n2=12)
    s32 = se.build_summary(key, A, B, 32, backend=backend)
    sbf = se.build_summary(key, A, B, 32, backend=backend, precision="bf16")
    for name in ("A_sketch", "B_sketch", "norm_A", "norm_B"):
        assert getattr(sbf, name).dtype == jnp.float32, name
    scale = float(jnp.abs(s32.A_sketch).max())
    assert float(jnp.max(jnp.abs(sbf.A_sketch - s32.A_sketch))) < 0.05 * scale
    np.testing.assert_allclose(np.asarray(sbf.norm_A), np.asarray(s32.norm_A),
                               rtol=2e-2)


# ---------------------------------------------------------------------------
# Structured-product paths (the engine-owned caller integrations)
# ---------------------------------------------------------------------------

def test_identity_product_summary_matches_manual(key):
    """A=I mapping: A_sketch is Pi itself, B_sketch = Pi @ G, exact norms."""
    G = jax.random.normal(key, (64, 48))
    s = se.identity_product_summary(key, G, 16)
    Pi = core.gaussian_pi(key, 16, 64)
    np.testing.assert_array_equal(np.asarray(s.A_sketch), np.asarray(Pi))
    np.testing.assert_allclose(np.asarray(s.B_sketch), np.asarray(Pi @ G),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s.norm_B),
                               np.linalg.norm(np.asarray(G), axis=0),
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(s.norm_A), np.ones(64))


def test_compress_leaf_stacked_matches_loop(key):
    """(L, n1, n2) stacked layer groups compress layer-by-layer identically
    to the looped 2D path (the batched engine mode)."""
    from repro.optim import grad_compression as gc
    cfg = gc.CompressionConfig(rank=2, sketch_k=16, als_iters=2)
    G = jax.random.normal(key, (2, 64, 72)) * 0.1
    stacked = gc.compress_leaf(key, G, cfg)
    assert stacked.shape == G.shape
    keys = jax.random.split(key, 2)
    for i in range(2):
        solo = gc.compress_leaf(keys[i], G[i], cfg)
        np.testing.assert_allclose(np.asarray(stacked[i]), np.asarray(solo),
                                   rtol=1e-4, atol=1e-5)


def test_smppca_through_engine_backends(key):
    """End-to-end Alg 1 quality is backend-independent."""
    A, B = planted_pair(key, 1024, 50, corr=0.4)
    errs = {}
    for backend in ("reference", "scan", "pallas"):
        res = core.smppca(key, A, B, r=3, k=128, m=6000, T=4,
                          backend=backend)
        errs[backend] = float(core.spectral_error(A, B, res.factors))
    for backend, e in errs.items():
        assert e < 0.8, (backend, errs)
    spread = max(errs.values()) - min(errs.values())
    assert spread < 0.05, errs


def test_srht_oversized_k_raises_named_valueerror(key):
    """srht with k > next_pow2(d) cannot sample k distinct rows: a
    descriptive ValueError naming the shapes, never a strippable assert."""
    import pytest
    from repro.core.summary_engine import srht_plan
    with pytest.raises(ValueError, match=r"k=100.*d=48"):
        srht_plan(key, 48, 100)
    A = jax.random.normal(key, (48, 6))
    B = jax.random.normal(jax.random.fold_in(key, 1), (48, 5))
    with pytest.raises(ValueError, match="next_pow2"):
        core.build_summary(key, A, B, 100, method="srht")
    # k exactly at the padded dimension is still legal
    assert srht_plan(key, 48, 64)[1].shape == (64,)
