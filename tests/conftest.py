"""Shared pytest fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches
must see the single real CPU device; multi-device tests spawn subprocesses
with their own flags (tests/dist/)."""
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def key():
    return jax.random.PRNGKey(0)


def planted_pair(key, d, n, decay=1.0, corr=None):
    """Synthetic (A, B) = G @ D with D_ii = 1/i^decay (the paper's generator).

    corr=None -> independent A, B; corr=sigma -> B = A + sigma * noise
    (columns drawn from a cone, the paper's favourable regime)."""
    kA, kB = jax.random.split(key)
    D = jnp.diag(1.0 / jnp.arange(1.0, n + 1.0) ** decay)
    A = jax.random.normal(kA, (d, n)) @ D
    if corr is None:
        B = jax.random.normal(kB, (d, n)) @ D
    else:
        B = A + corr * jax.random.normal(kB, (d, n)) @ D
    return A, B


def figure_key(name):
    """The key of one paper-figure check: ``PRNGKey(0)`` folded by the crc32
    of the figure's name, so each figure draws its own data."""
    return jax.random.fold_in(jax.random.PRNGKey(0),
                              zlib.crc32(name.encode()) % 2**31)


def cone_pair(key, d, n, theta):
    """Unit columns drawn from a cone of angle theta around one direction,
    with random signs: A's columns and B's columns are the two halves (the
    paper's Fig 2(b) construction; narrower cones are more aligned)."""
    kx, kt, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (d, 1))
    x = x / jnp.linalg.norm(x)
    t = jax.random.normal(kt, (d, 2 * n)) * (math.tan(theta / 2) / math.sqrt(d))
    sign = jnp.where(jax.random.bernoulli(ks, 0.5, (2 * n,)), 1.0, -1.0)
    y = (x + t) * sign[None, :]
    y = y / jnp.linalg.norm(y, axis=0)
    return y[:, :n], y[:, n:]


def gaussian_pair(key, d=192, n1=11, n2=7):
    """Plain iid-normal (A, B) — the generic parity/monoid test input
    (shared here; previously inlined per test module)."""
    kA, kB = jax.random.split(key)
    return (jax.random.normal(kA, (d, n1)), jax.random.normal(kB, (d, n2)))


def spectrum_values(kind, q=10):
    """Named singular-value profiles for the known-spectrum fixtures."""
    i = np.arange(q, dtype=np.float64)
    if kind == "fast":                 # geometric decay: clear rank gaps
        s = 2.0 ** -i
    elif kind == "slow":               # polynomial decay: heavy tail
        s = 1.0 / np.sqrt(1.0 + i)
    elif kind == "rank_deficient":     # exact rank q//2: zero tail
        s = np.where(i < q // 2, 2.0 ** -i, 0.0)
    else:
        raise ValueError(f"unknown spectrum kind {kind!r}")
    return jnp.asarray(s, jnp.float32)


def known_spectrum_pair(key, d, n1, n2, spectrum):
    """(A, B, M) with A^T B == M == U0 diag(spectrum) V0^T *exactly*.

    A = W (orthonormal columns), B = W @ M, so A^T B = M and M's singular
    values are the given spectrum — the ground truth every ErrorEngine /
    adaptive-rank assertion compares against.
    """
    q = spectrum.shape[0]
    assert q <= min(n1, n2), (q, n1, n2)
    kW, kU, kV = jax.random.split(key, 3)
    W, _ = jnp.linalg.qr(jax.random.normal(kW, (d, n1)))
    U0, _ = jnp.linalg.qr(jax.random.normal(kU, (n1, q)))
    V0, _ = jnp.linalg.qr(jax.random.normal(kV, (n2, q)))
    M = (U0 * spectrum[None, :]) @ V0.T
    return W, W @ M, M


def drifting_spectrum_pair(key, d=256, n1=14, n2=12, q=3):
    """Two-phase piecewise-stationary stream with disjoint top subspaces.

    Returns ``((A1, B1, M1, U1), (A2, B2, M2, U2))``: phase i satisfies
    ``Ai^T Bi == Mi`` exactly with top-q left singular subspace ``Ui``, and
    ``U1 ⟂ U2`` (drawn as disjoint column blocks of one orthonormal basis).
    Phase 1 carries 4x the singular mass, so after the flip a cumulative
    (vanilla) summary keeps answering ``U1`` while a decayed/windowed
    summary recovers ``U2`` — drift tests assert subspace recovery instead
    of eyeballing error curves.
    """
    kW1, kW2, kU, kV1, kV2 = jax.random.split(key, 5)
    U_all, _ = jnp.linalg.qr(jax.random.normal(kU, (n1, 2 * q)))
    U1, U2 = U_all[:, :q], U_all[:, q:]
    V1, _ = jnp.linalg.qr(jax.random.normal(kV1, (n2, q)))
    V2, _ = jnp.linalg.qr(jax.random.normal(kV2, (n2, q)))
    # flat within-phase spectrum: the drift IS the subspace flip, and a
    # clean top-q gap keeps recovery assertions well above the sketch noise
    M1 = 8.0 * U1 @ V1.T
    M2 = 4.0 * U2 @ V2.T
    W1, _ = jnp.linalg.qr(jax.random.normal(kW1, (d, n1)))
    W2, _ = jnp.linalg.qr(jax.random.normal(kW2, (d, n1)))
    return (W1, W1 @ M1, M1, U1), (W2, W2 @ M2, M2, U2)


@pytest.fixture()
def drifting_pair(key):
    """The two-phase drifting stream at the default test geometry."""
    return drifting_spectrum_pair(key)


@pytest.fixture(params=["fast", "slow", "rank_deficient"])
def spectrum_case(request, key):
    """(kind, A, B, M, spectrum) across the three known-spectrum profiles:
    fast decay, slow decay, and exactly rank-deficient."""
    kind = request.param
    s = spectrum_values(kind)
    A, B, M = known_spectrum_pair(key, 384, 14, 12, s)
    return kind, A, B, M, s
