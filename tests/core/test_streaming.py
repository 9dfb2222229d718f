"""Streaming mergeable-summary tests.

The contract (docs/streaming.md): chunked ingestion, any merge order, and
the one-shot ``build_summary`` all produce the same summary — sequential
same-chunk ingestion bit-identical to the scan backend, merge commutative
bit-for-bit, arbitrary reassociation to float tolerance; checkpoint
round-trips and serving sessions are bit-exact.
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
from repro.core import streaming
from repro.core.summary_engine import build_summary
from tests.conftest import gaussian_pair as _pair


def _ingest(summ, key, A, B, chunk):
    state = summ.init(key, (A.shape[0], A.shape[1], B.shape[1]))
    for off in range(0, A.shape[0], chunk):
        state = summ.update(state, A[off:off + chunk], B[off:off + chunk],
                            off)
    return state


def _assert_bit_equal(got, want, msg=""):
    for name in ("A_sketch", "B_sketch", "norm_A", "norm_B"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            err_msg=f"{msg}{name}")


def _assert_close(got, want, rtol=2e-4):
    for name in ("A_sketch", "B_sketch", "norm_A", "norm_B"):
        g, w = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        np.testing.assert_allclose(
            g, w, rtol=rtol, atol=1e-5 * max(np.abs(w).max(), 1.0),
            err_msg=name)


# ---------------------------------------------------------------------------
# Chunked-vs-one-shot parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["gaussian", "srht"])
def test_sequential_chunks_bit_identical_to_scan(key, method):
    """Sequential ingestion at chunk c == build_summary(scan, block=c),
    bit-for-bit: the update performs the scan body's exact float ops."""
    A, B = _pair(key, d=256)
    summ = core.StreamingSummarizer(16, method=method)
    s = summ.finalize(_ingest(summ, key, A, B, chunk=64))
    scan = build_summary(key, A, B, 16, method=method, backend="scan",
                         block=64)
    _assert_bit_equal(s, scan, f"{method}/")


@pytest.mark.parametrize("method", ["gaussian", "srht"])
def test_chunked_matches_reference(key, method):
    """Any chunking agrees with the materialized-operator reference to
    float-reassociation tolerance (incl. a partial final chunk: 192 % 80)."""
    A, B = _pair(key)
    summ = core.StreamingSummarizer(16, method=method)
    ref = build_summary(key, A, B, 16, method=method, backend="reference")
    for chunk in (48, 80, 192):
        s = summ.finalize(_ingest(summ, key, A, B, chunk))
        _assert_close(s, ref)


def test_update_rows_arbitrary_order(key):
    """Shuffled explicit-id chunks (the co-occurrence stream) match the
    one-shot summary; a second shuffle matches the first to tolerance."""
    A, B = _pair(key)
    d = A.shape[0]
    summ = core.StreamingSummarizer(16)
    ref = build_summary(key, A, B, 16, backend="reference")
    for seed in (0, 1):
        perm = np.random.default_rng(seed).permutation(d)
        state = summ.init(key, (d, A.shape[1], B.shape[1]))
        for off in range(0, d, 48):
            ids = jnp.asarray(perm[off:off + 48])
            state = summ.update_rows(state, ids, A[ids], B[ids])
        assert int(state.rows_seen) == d
        _assert_close(summ.finalize(state), ref)


def test_summarize_chunks_convenience(key):
    A, B = _pair(key)
    summ = core.StreamingSummarizer(16)
    s = summ.summarize_chunks(
        key, (A.shape[0], A.shape[1], B.shape[1]),
        ((A[off:off + 64], B[off:off + 64])
         for off in range(0, A.shape[0], 64)))
    _assert_bit_equal(s, build_summary(key, A, B, 16, backend="scan",
                                       block=64))


# ---------------------------------------------------------------------------
# Monoid laws
# ---------------------------------------------------------------------------

def test_merge_commutative_bitwise(key):
    """merge(s1, s2) == merge(s2, s1) bit-for-bit (float add commutes)."""
    A, B = _pair(key)
    summ = core.StreamingSummarizer(16, method="srht")
    empty = summ.init(key, (192, 11, 7))
    s1 = summ.update(empty, A[:96], B[:96], 0)
    s2 = summ.update(empty, A[96:], B[96:], 96)
    m12, m21 = summ.merge(s1, s2), summ.merge(s2, s1)
    for f in ("A_acc", "B_acc", "na2", "nb2", "rows_seen"):
        np.testing.assert_array_equal(np.asarray(getattr(m12, f)),
                                      np.asarray(getattr(m21, f)), err_msg=f)


@settings(deadline=None, max_examples=8)
@given(i=st.sampled_from([32, 64, 96]), j=st.sampled_from([128, 160]))
def test_merge_associative_property(i, j):
    """finalize(merge(merge(a,b),c)) ~= finalize(merge(a,merge(b,c))) for
    arbitrary three-way splits (property test via tests/_hyp.py)."""
    key = jax.random.PRNGKey(3)
    A, B = _pair(key)
    summ = core.StreamingSummarizer(8)
    empty = summ.init(key, (192, 11, 7))
    a = summ.update(empty, A[:i], B[:i], 0)
    b = summ.update(empty, A[i:j], B[i:j], i)
    c = summ.update(empty, A[j:], B[j:], j)
    left = summ.finalize(summ.merge(summ.merge(a, b), c))
    right = summ.finalize(summ.merge(a, summ.merge(b, c)))
    _assert_close(left, right, rtol=2e-5)
    assert int(summ.merge(summ.merge(a, b), c).rows_seen) == 192


@settings(deadline=None, max_examples=6)
@given(chunk=st.sampled_from([32, 64, 96]), order_seed=st.integers(0, 99))
def test_any_merge_order_matches_one_shot(chunk, order_seed):
    """Per-chunk partial states merged in a random order match the one-shot
    reference summary (property test)."""
    key = jax.random.PRNGKey(4)
    A, B = _pair(key)
    summ = core.StreamingSummarizer(8)
    empty = summ.init(key, (192, 11, 7))
    parts = [summ.update(empty, A[off:off + chunk], B[off:off + chunk], off)
             for off in range(0, 192, chunk)]
    rng = np.random.default_rng(order_seed)
    rng.shuffle(parts)
    merged = parts[0]
    for p in parts[1:]:
        merged = streaming.merge_states(merged, p)
    _assert_close(summ.finalize(merged),
                  build_summary(key, A, B, 8, backend="reference"))


def test_tree_merge_matches_sequential(key):
    A, B = _pair(key)
    summ = core.StreamingSummarizer(16)
    empty = summ.init(key, (192, 11, 7))
    parts = [summ.update(empty, A[off:off + 48], B[off:off + 48], off)
             for off in range(0, 192, 48)]
    _assert_close(summ.finalize(core.tree_merge(parts)),
                  summ.finalize(_ingest(summ, key, A, B, 48)), rtol=2e-5)


def test_empty_chunk_is_identity(key):
    """Zero-row chunks are absorbed as no-ops (the monoid identity)."""
    summ = core.StreamingSummarizer(8)
    state = summ.update(summ.init(key, (64, 4, 3)), jnp.ones((16, 4)),
                        jnp.ones((16, 3)), 0)
    after = summ.update(state, jnp.zeros((0, 4)), jnp.zeros((0, 3)), 16)
    after = summ.update_rows(after, jnp.zeros((0,), jnp.int32),
                             jnp.zeros((0, 4)), jnp.zeros((0, 3)))
    for f in ("A_acc", "B_acc", "na2", "nb2", "rows_seen", "row_high"):
        np.testing.assert_array_equal(np.asarray(getattr(after, f)),
                                      np.asarray(getattr(state, f)),
                                      err_msg=f)
    # an empty A with a non-empty B is a mismatch, not an identity
    with pytest.raises(ValueError, match="row counts differ"):
        summ.update(state, jnp.zeros((0, 4)), jnp.ones((16, 3)), 16)
    with pytest.raises(ValueError, match="row counts differ"):
        summ.update_rows(state, jnp.zeros((0,), jnp.int32),
                         jnp.zeros((0, 4)), jnp.ones((16, 3)))


def test_resume_cursor_is_high_water_mark(key, tmp_path):
    """An out-of-order pass checkpointed and resumed continues appending
    after the highest absorbed row, not after rows_seen."""
    from repro.ckpt import checkpoint
    from repro.serve.engine import SketchService
    A, B = _pair(key, d=128, n1=10, n2=8)
    svc = SketchService(k=8, backend="scan", block=32)
    sid = svc.open_stream(key, 128, 10, 8)
    svc.append(sid, A[32:64], B[32:64], row_offset=32)   # out of order first
    state = svc.close_stream(sid)
    assert int(state.rows_seen) == 32 and int(state.row_high) == 64
    checkpoint.save_stream_state(str(tmp_path), 0, state)
    restored = checkpoint.restore_stream_state(
        str(tmp_path), like=core.StreamingSummarizer(8).init(
            key, (128, 10, 8)))
    sid2 = svc.open_stream(key, 128, 10, 8, state=restored)
    svc.append(sid2, A[64:96], B[64:96])        # default cursor -> row 64
    svc.append(sid2, A[96:], B[96:])
    svc.append(sid2, A[:32], B[:32], row_offset=0)       # backfill the gap
    # chunk order differs from sequential -> reassociation tolerance
    _assert_close(svc.query(sid2),
                  build_summary(key, A, B, 8, backend="scan", block=32),
                  rtol=2e-5)


@pytest.mark.parametrize("method", ["gaussian", "srht"])
def test_out_of_range_rows_rejected(key, method):
    """Row ids outside [0, d_total) raise instead of silently corrupting
    the summary (SRHT would clamp into the sign vector)."""
    summ = core.StreamingSummarizer(8, method=method)
    state = summ.init(key, (64, 4, 3))
    A = jnp.ones((16, 4))
    B = jnp.ones((16, 3))
    with pytest.raises(ValueError, match="d_total"):
        summ.update(state, A, B, row_offset=64)
    with pytest.raises(ValueError, match="d_total"):
        summ.update_rows(state, jnp.array([-1] + list(range(15))), A, B)
    summ.update(state, A, B, row_offset=48)         # last valid chunk is fine


def test_open_stream_resume_validation(key):
    """Resuming a session with a mismatched state (shape, key, or method)
    raises instead of silently breaking the stream_factors parity."""
    from repro.serve.engine import SketchService
    svc = SketchService(k=8, backend="scan", block=32)
    summ = core.StreamingSummarizer(8)
    state = summ.init(key, (64, 4, 3))
    with pytest.raises(ValueError, match="does not match"):
        svc.open_stream(key, 64, 5, 3, state=state)      # wrong n1
    with pytest.raises(ValueError, match="does not match"):
        svc.open_stream(key, 128, 4, 3, state=state)     # wrong d
    with pytest.raises(ValueError, match="different base key"):
        svc.open_stream(jax.random.PRNGKey(99), 64, 4, 3, state=state)
    srht_state = core.StreamingSummarizer(8, method="srht").init(
        key, (64, 4, 3))
    with pytest.raises(ValueError, match="method"):
        svc.open_stream(key, 64, 4, 3, state=srht_state)
    sid = svc.open_stream(key, 64, 4, 3, state=state)    # matching: fine
    assert svc.append(sid, jnp.ones((32, 4)), jnp.ones((32, 3))) == 32


def test_merge_guards(key):
    summ = core.StreamingSummarizer(8)
    s_a = summ.init(key, (64, 4, 3))
    s_b = summ.init(key, (64, 5, 3))
    with pytest.raises(ValueError, match="shapes"):
        streaming.merge_states(s_a, s_b)
    s_srht = core.StreamingSummarizer(8, method="srht").init(key, (64, 4, 3))
    with pytest.raises(ValueError, match="gaussian and srht"):
        streaming.merge_states(s_a, s_srht)
    with pytest.raises(ValueError, match="method"):
        core.StreamingSummarizer(8, method="nope")
    with pytest.raises(ValueError, match="tree_merge"):
        streaming.tree_merge([])


# ---------------------------------------------------------------------------
# Checkpoint round-trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["gaussian", "srht"])
def test_checkpoint_roundtrip_bitwise(key, tmp_path, method):
    """save mid-pass -> restore -> continue == uninterrupted, bit-for-bit;
    the manifest records coverage."""
    from repro.ckpt import checkpoint
    A, B = _pair(key)
    summ = core.StreamingSummarizer(16, method=method)
    half = summ.update(summ.init(key, (192, 11, 7)), A[:96], B[:96], 0)
    checkpoint.save_stream_state(str(tmp_path), 96, half)
    manifest = checkpoint.read_manifest(str(tmp_path))
    assert manifest["extra"]["rows_seen"] == 96
    assert manifest["extra"]["kind"] == "stream_state"
    assert manifest["extra"]["srht"] == (method == "srht")
    restored = checkpoint.restore_stream_state(
        str(tmp_path), like=summ.init(key, (192, 11, 7)))
    full_resumed = summ.finalize(summ.update(restored, A[96:], B[96:], 96))
    full_direct = summ.finalize(summ.update(half, A[96:], B[96:], 96))
    _assert_bit_equal(full_resumed, full_direct)


# ---------------------------------------------------------------------------
# Serving accumulator sessions
# ---------------------------------------------------------------------------

def test_stream_session_matches_one_shot_flush(key):
    """open_stream/append/query == submit/flush bit-for-bit when chunks
    align with the service block, and stream_factors == flush_factors to
    f32 rounding (the flush estimates a batch of one under vmap, which XLA
    may fuse differently)."""
    from repro.serve.engine import SketchService
    A, B = _pair(key, d=128, n1=10, n2=8)
    svc = SketchService(k=8, backend="scan", block=32)
    sid = svc.open_stream(key, 128, 10, 8)
    for off in range(0, 128, 32):
        seen = svc.append(sid, A[off:off + 32], B[off:off + 32])
    assert seen == 128
    ticket = svc.submit(key, A, B)
    flushed = svc.flush()[ticket]
    _assert_bit_equal(svc.query(sid), flushed)

    ticket = svc.submit(key, A, B)
    ff = svc.flush_factors(r=2, m=200, T=2)[ticket]
    sf = svc.stream_factors(sid, r=2, m=200, T=2)
    for got, want in ((sf.factors.U, ff.factors.U),
                      (sf.factors.V, ff.factors.V)):
        want = np.asarray(want)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())
    state = svc.close_stream(sid)
    assert int(state.rows_seen) == 128
    assert sid not in svc._streams


def test_stream_session_resumes_from_checkpoint(key, tmp_path):
    """A checkpointed state seeds a fresh session (open_stream(state=...))."""
    from repro.ckpt import checkpoint
    from repro.serve.engine import SketchService
    A, B = _pair(key, d=128, n1=10, n2=8)
    svc = SketchService(k=8, backend="scan", block=32)
    sid = svc.open_stream(key, 128, 10, 8)
    svc.append(sid, A[:32], B[:32])
    svc.append(sid, A[32:64], B[32:64])
    checkpoint.save_stream_state(str(tmp_path), 64, svc.close_stream(sid))

    svc2 = SketchService(k=8, backend="scan", block=32)
    summ = core.StreamingSummarizer(8)
    restored = checkpoint.restore_stream_state(
        str(tmp_path), like=summ.init(key, (128, 10, 8)))
    sid2 = svc2.open_stream(key, 128, 10, 8, state=restored)
    svc2.append(sid2, A[64:96], B[64:96])             # cursor resumed at 64
    assert svc2.append(sid2, A[96:], B[96:]) == 128
    _assert_bit_equal(svc2.query(sid2),
                      build_summary(key, A, B, 8, backend="scan", block=32))


# ---------------------------------------------------------------------------
# Distributed tree-reduce
# ---------------------------------------------------------------------------

@pytest.mark.dist
def test_distributed_streaming_tree_reduce():
    """Per-device partial states merged by one psum (2-shard CPU mesh, slab
    chunking) match the reference summary, both methods."""
    from tests.dist.helpers import run_with_devices
    out = run_with_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro import core
    mesh = Mesh(np.array(jax.devices()), ("shard",))
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (256, 20))
    B = jax.random.normal(jax.random.fold_in(key, 1), (256, 14))
    for method in ("gaussian", "srht"):
        ref = core.build_summary(key, A, B, 32, method=method,
                                 backend="reference")
        # slab=96 leaves a trailing partial slab (256 = 96+96+64): the
        # rounding guard must keep every slab divisible by the 2 shards
        got = core.distributed_streaming_summary(
            mesh, "shard", key, A, B, 32, method=method, slab=96)
        for name in ("A_sketch", "B_sketch", "norm_A", "norm_B"):
            g = np.asarray(getattr(got, name))
            w = np.asarray(getattr(ref, name))
            np.testing.assert_allclose(
                g, w, rtol=2e-4, atol=1e-5 * max(np.abs(w).max(), 1.0),
                err_msg=f"{method}/{name}")
    print("DIST_STREAM_OK")
    """, n_devices=2)
    assert "DIST_STREAM_OK" in out


# ---------------------------------------------------------------------------
# Gradient taps ride the same monoid
# ---------------------------------------------------------------------------

def test_tap_state_monoid(key):
    """accumulate_taps is merge_states on wrapped states; decompress_tap
    finalizes through streaming.finalize_state."""
    from repro.train import sketched_dense as sd
    k1, k2 = jax.random.split(key)
    def mk(kk):
        ks = jax.random.split(kk, 4)
        return {"a": jax.random.normal(ks[0], (8, 6)),
                "b": jax.random.normal(ks[1], (8, 5)),
                "na2": jnp.abs(jax.random.normal(ks[2], (6,))),
                "nb2": jnp.abs(jax.random.normal(ks[3], (5,)))}
    t1, t2 = mk(k1), mk(k2)
    acc = sd.accumulate_taps(t1, t2)
    for f in ("a", "b", "na2", "nb2"):
        np.testing.assert_array_equal(np.asarray(acc[f]),
                                      np.asarray(t1[f] + t2[f]), err_msg=f)
    s = streaming.finalize_state(sd.tap_state(t1))
    np.testing.assert_allclose(np.asarray(s.norm_A),
                               np.sqrt(np.asarray(t1["na2"])), rtol=1e-6)
    dw = sd.decompress_tap(key, t1, sd.TapConfig(sketch_k=8, rank=2,
                                                 als_iters=2))
    assert dw.shape == (6, 5)
