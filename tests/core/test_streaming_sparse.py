"""Sparse rows (``SparseRows``) through the streaming summary: the same
summary as the dense path on the densified chunk, padding that adds
nothing, merges with dense-fed states, the whole path to factors, and the
options the sparse path refuses.

A bag-of-words-like chunk: 256 documents over 1,000 words with Zipf column
weights, one word in every document (the hot column the sparse kernel's
partial sums exist for). Tolerance: both paths sum f32 products in f32, in
different orders (the dense path in MXU/BLAS blocks, the sparse path by
column in the kernel's partial sums), so entries agree to a few f32
roundings of their largest terms: 2e-5 relative to each block's largest
entry, against ~1e-3 for the projection cut to bf16. One chunk fed as both
operands takes one column pass (``repro.sparse.shared_pass``), whose
summary equals the two-pass summary of an equal copy bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import StreamingSummarizer, WindowedSummarizer, merge_states
from repro.core.estimation_engine import estimate_product
from repro.core.types import SparseRows

T, N, K, P = 256, 1000, 32, 4
RTOL = 2e-5
BLOCKS = ("A_sketch", "B_sketch", "norm_A", "norm_B", "probes")


def _chunk(seed, docs=T, density=0.02):
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, N + 1)
    keep = rng.random((docs, N)) < np.minimum(1.0, density * N * w / w.sum()
                                              * 10)
    X = keep * rng.integers(1, 6, (docs, N))
    X[:, 7] = rng.integers(1, 9, docs)          # in every document
    return jnp.asarray(X, jnp.float32)


def _sparse(X, extra=64):
    return SparseRows.from_dense(X, int(jnp.count_nonzero(X)) + extra)


def _close(got, want):
    for name in BLOCKS:
        g, w = getattr(got, name), getattr(want, name)
        assert (g is None) == (w is None), name
        if w is None:
            continue
        scale = float(jnp.max(jnp.abs(w)))
        assert float(jnp.max(jnp.abs(g - w))) <= RTOL * scale, name


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(3)


@pytest.mark.parametrize("probes", [0, P])
@pytest.mark.parametrize("entry", ["update", "ingest", "update_rows"])
def test_sparse_chunks_match_the_dense_path(key, probes, entry):
    summ = StreamingSummarizer(k=K, probes=probes)
    chunks = [_chunk(s) for s in (1, 2)]
    dense = summ.init(key, (4 * T, N, N))
    sparse = dense
    for i, X in enumerate(chunks):
        dense = summ.update(dense, X, X, T + i * T)
    if entry == "ingest":
        sparse = summ.ingest(sparse, [(_sparse(X), _sparse(X))
                                      for X in chunks], row_offset=T)
    for i, X in enumerate(chunks if entry != "ingest" else []):
        S = _sparse(X)
        if entry == "update":
            sparse = summ.update(sparse, S, S, T + i * T)
        else:
            ids = T + i * T + jnp.arange(T)
            sparse = summ.update_rows(sparse, ids, S, S)
    assert int(sparse.rows_seen) == 2 * T
    assert int(sparse.row_high) == int(dense.row_high) == 3 * T
    _close(summ.finalize(sparse), summ.finalize(dense))


SHARED = "repro.sparse.shared_pass"
STATE = ("A_acc", "B_acc", "na2", "nb2", "probe_acc")


def _shared_passes():
    return telemetry.counts().get(SHARED, 0)


def _copy(S):
    return SparseRows(*(jnp.array(leaf, copy=True)
                        for leaf in (S.rows, S.cols, S.vals)), S.shape)


@pytest.mark.parametrize("feed,passes", [
    ("same object", 1), ("same leaves", 1), ("equal copy", 0),
    ("dense", 0)])
def test_one_chunk_as_both_operands_takes_one_column_pass(key, feed,
                                                          passes):
    """The one-pass summary equals the two-pass summary of an equal copy
    bit for bit; the count reads one per chunk that took the one pass."""
    summ = StreamingSummarizer(k=K, probes=P)
    X = _chunk(12)
    S = _sparse(X)
    init = summ.init(key, (T, N, N))
    two_pass = summ.update(init, S, _copy(S), 0)
    A, B = {"same object": (S, S),
            "same leaves": (S, SparseRows(S.rows, S.cols, S.vals, S.shape)),
            "equal copy": (S, _copy(S)), "dense": (X, X)}[feed]
    before = _shared_passes()
    state = summ.update(init, A, B, 0)
    assert _shared_passes() - before == passes
    if feed == "dense":
        _close(summ.finalize(state), summ.finalize(two_pass))
        return
    for name in STATE:
        assert bool(jnp.all(getattr(state, name)
                            == getattr(two_pass, name))), name


def test_ingest_stages_a_host_chunk_fed_as_both_operands_once(key,
                                                              monkeypatch):
    summ = StreamingSummarizer(k=K, probes=P)
    chunks = [SparseRows(*(np.asarray(leaf) for leaf in
                           (S.rows, S.cols, S.vals)), S.shape)
              for S in (_sparse(_chunk(s)) for s in (13, 14))]
    handed = []
    update = summ.update

    def spy(state, A, B, off):
        handed.append((A, B))
        return update(state, A, B, off)

    monkeypatch.setattr(summ, "update", spy)
    init = summ.init(key, (2 * T, N, N))
    before = _shared_passes()
    state = summ.ingest(init, [(S, S) for S in chunks], row_offset=0)
    assert _shared_passes() - before == len(chunks)
    assert len(handed) == len(chunks)
    for A, B in handed:
        assert A is B and isinstance(A.vals, jax.Array)
    want = init
    for i, S in enumerate(chunks):
        D = jax.device_put(S)
        want = update(want, D, D, i * T)
    for name in STATE:
        assert bool(jnp.all(getattr(state, name) == getattr(want, name))), \
            name


def test_a_chunk_of_padding_adds_exactly_nothing(key):
    summ = StreamingSummarizer(k=K, probes=P)
    rng = np.random.default_rng(0)
    pad = SparseRows(jnp.asarray(rng.integers(0, T, 512), jnp.int32),
                     jnp.asarray(rng.integers(0, N, 512), jnp.int32),
                     jnp.zeros((512,), jnp.float32), (T, N))
    state = summ.update(summ.init(key, (T, N, N)), pad, pad, 0)
    s = summ.finalize(state)
    for name in BLOCKS:
        assert not bool(jnp.any(getattr(s, name) != 0)), name
    assert int(state.rows_seen) == T


def test_padding_does_not_change_the_summary(key):
    summ = StreamingSummarizer(k=K, probes=P)
    X = _chunk(4)
    init = summ.init(key, (T, N, N))
    tight, loose = _sparse(X, extra=0), _sparse(X, extra=5000)
    _close(summ.finalize(summ.update(init, loose, loose, 0)),
           summ.finalize(summ.update(init, tight, tight, 0)))


def test_a_sparse_fed_state_merges_with_a_dense_fed_one(key):
    summ = StreamingSummarizer(k=K, probes=P)
    X, Y = _chunk(5), _chunk(6)
    init = summ.init(key, (2 * T, N, N))
    mixed = merge_states(summ.update(init, _sparse(X), _sparse(X), 0),
                         summ.update(init, Y, Y, T))
    dense = summ.update(summ.update(init, X, X, 0), Y, Y, T)
    _close(summ.finalize(mixed), summ.finalize(dense))
    assert int(mixed.rows_seen) == 2 * T


def _spectral_error(M, U, V):
    return float(np.linalg.norm(M - np.asarray(U) @ np.asarray(V).T, 2))


def test_sparse_ingest_to_factors_matches_the_exact_top_r(key):
    """Sparse ingest -> finalize -> rescaled-JL factors of A^T A: as close
    to the exact top-r as the dense path's factors from the same key."""
    r, chunks = 4, [_chunk(s) for s in range(10, 14)]
    summ = StreamingSummarizer(k=128)
    init = summ.init(key, (len(chunks) * T, N, N))
    sparse = summ.ingest(init, [(_sparse(X), _sparse(X)) for X in chunks],
                         row_offset=0)
    dense = summ.ingest(init, [(X, X) for X in chunks], row_offset=0)
    A = np.concatenate([np.asarray(X, np.float64) for X in chunks])
    M = A.T @ A
    s = np.linalg.svd(M, compute_uv=False)
    errors = []
    for state in (sparse, dense):
        res = estimate_product(jax.random.fold_in(key, 9),
                               summ.finalize(state), r,
                               method="rescaled_jl", T=6)
        errors.append(_spectral_error(M, res.factors.U, res.factors.V))
    sparse_err, dense_err = errors
    assert sparse_err <= 1.01 * dense_err + 1e-6 * s[0]
    # and the factors hold the dominant part of the spectrum
    assert sparse_err < 0.2 * s[0]


@pytest.mark.parametrize("kw,word", [
    (dict(method="srht"), "srht"), (dict(cosketch=2), "cosketch"),
    (dict(decay=0.5), "decay"), (dict(precision="bf16"), "bf16")])
def test_unsupported_options_refuse_a_sparse_chunk(key, kw, word):
    summ = StreamingSummarizer(k=K, **kw)
    S = _sparse(_chunk(7))
    with pytest.raises(NotImplementedError, match="SparseRows") as err:
        summ.update(summ.init(key, (T, N, N)), S, S, 0)
    assert word in str(err.value)


@pytest.mark.parametrize("call", ["update", "update_rows", "ingest"])
def test_the_windowed_summarizer_refuses_a_sparse_chunk(key, call):
    win = WindowedSummarizer(k=K, n_buckets=2)
    S = _sparse(_chunk(8))
    w = win.init(key, (T, N, N))
    with pytest.raises(NotImplementedError, match="SparseRows"):
        if call == "update":
            win.update(w, S, S, 0)
        elif call == "update_rows":
            win.update_rows(w, jnp.arange(T), S, S)
        else:
            win.ingest(w, [(S, S)], row_offset=0)


def test_a_sparse_and_a_dense_operand_are_refused(key):
    summ = StreamingSummarizer(k=K)
    X = _chunk(9)
    with pytest.raises(ValueError, match="both"):
        summ.update(summ.init(key, (T, N, N)), _sparse(X), X, 0)


def test_sparse_rows_round_trip_and_capacity():
    X = _chunk(11, docs=16)
    S = _sparse(X, extra=3)
    assert S.shape == (16, N) and S.capacity == int(jnp.count_nonzero(X)) + 3
    assert bool(jnp.all(S.todense() == X))
    leaves, treedef = jax.tree_util.tree_flatten(S)
    assert len(leaves) == 3
    assert jax.tree_util.tree_unflatten(treedef, leaves).shape == S.shape
    with pytest.raises(ValueError, match="capacity"):
        SparseRows.from_dense(X, 4)
