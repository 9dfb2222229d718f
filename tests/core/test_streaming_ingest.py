"""Double-buffered ingest + the compressed wire format.

Property-tests the PR's two streaming contracts:

* **Ingest bit-parity** — ``StreamingSummarizer.ingest`` (any prefetch
  depth, plain or windowed, via the service's ``append_async``) produces
  the bit-identical state to the synchronous ``update`` loop: pipelining
  changes *when* chunks are staged, never *what* is accumulated.
* **Compression laws** — ``decompress(compress(s))`` at f32 is
  bit-identical to the settled state (structure included); norm and probe
  blocks round-trip bit-exactly at EVERY precision; quantized merge error
  stays within the probe-measured ``wire_error`` bound; ``wire_pack`` /
  ``wire_unpack`` round-trips every leaf; compressed checkpoints restore
  through the same laws.
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

from repro import telemetry
from repro.core import streaming
from repro.core.streaming import (
    StreamingSummarizer, WindowedSummarizer, WireSpec, choose_wire_spec,
    compress_state, decompress_state, tree_merge, wire_bytes, wire_error,
    wire_pack, wire_unpack)
from repro.ckpt import checkpoint

_KEY = jax.random.PRNGKey(42)
_D, _NA, _NB = 96, 9, 7


def _pair(key=_KEY, d=_D):
    kA, kB = jax.random.split(key)
    return (jax.random.normal(kA, (d, _NA)), jax.random.normal(kB, (d, _NB)))


def _stream_state(*, probes=4, cosketch=0, decay=1.0, method="gaussian",
                  d=_D):
    summ = StreamingSummarizer(8, method=method, probes=probes,
                               cosketch=cosketch, decay=decay)
    A, B = _pair(d=d)
    st = summ.init(_KEY, (d, _NA, _NB))
    st = summ.update(st, A, B, 0)
    return summ, st


def _assert_tree_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    assert (jax.tree_util.tree_structure(a)
            == jax.tree_util.tree_structure(b))
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# ingest bit-parity


@settings(deadline=None, max_examples=8)
@given(prefetch=st.sampled_from([0, 1, 2, 4]),
       chunk=st.sampled_from([16, 32, 96]))
def test_ingest_bit_parity_with_update_loop(prefetch, chunk):
    summ = StreamingSummarizer(8, probes=4, cosketch=4)
    A, B = _pair()
    ref = summ.init(_KEY, (_D, _NA, _NB))
    for off in range(0, _D, chunk):
        ref = summ.update(ref, A[off:off + chunk], B[off:off + chunk], off)
    got = summ.ingest(
        summ.init(_KEY, (_D, _NA, _NB)),
        ((A[off:off + chunk], B[off:off + chunk])
         for off in range(0, _D, chunk)),
        prefetch=prefetch)
    _assert_tree_equal(got, ref)


def test_ingest_resumes_from_row_high():
    summ = StreamingSummarizer(8)
    A, B = _pair()
    ref = summ.init(_KEY, (_D, _NA, _NB))
    ref = summ.update(ref, A[:32], B[:32], 0)
    ref = summ.update(ref, A[32:64], B[32:64], 32)
    got = summ.ingest(summ.init(_KEY, (_D, _NA, _NB)), [(A[:32], B[:32])])
    got = summ.ingest(got, [(A[32:64], B[32:64])])   # offset = row_high
    _assert_tree_equal(got, ref)


@pytest.mark.parametrize("prefetch", [0, 2])
def test_ingest_records_one_call_and_its_children(prefetch):
    summ = StreamingSummarizer(8, probes=4)
    A, B = _pair()
    n = _D // 32
    summ.ingest(summ.init(_KEY, (_D, _NA, _NB)),
                ((A[off:off + 32], B[off:off + 32])
                 for off in range(0, _D, 32)),
                prefetch=prefetch)
    occ = telemetry.occurrences()
    call = max(i for i, o in enumerate(occ) if o.name == "repro.ingest")
    children = [o.name for o in occ if o.parent == call]
    # the source is pulled once more, to find that it has ended
    assert sorted(children) == sorted(["repro.ingest.feed"] * (n + 1)
                                      + ["repro.ingest.stage"] * n
                                      + ["repro.ingest.update"] * n)
    assert occ[call].children == len(children)
    assert occ[call].parent is None


def test_ingest_rejects_bad_prefetch():
    summ = StreamingSummarizer(8)
    st = summ.init(_KEY, (_D, _NA, _NB))
    for bad in (-1, True, 1.5):
        with pytest.raises(ValueError):
            summ.ingest(st, [], prefetch=bad)


def test_windowed_ingest_matches_head_bucket_updates():
    ws = WindowedSummarizer(8, n_buckets=2, probes=4)
    A, B = _pair()
    ref = ws.init(_KEY, (_D, _NA, _NB))
    for off in range(0, 64, 32):
        ref = ws.update(ref, A[off:off + 32], B[off:off + 32], off)
    got = ws.ingest(ws.init(_KEY, (_D, _NA, _NB)),
                    ((A[off:off + 32], B[off:off + 32])
                     for off in range(0, 64, 32)),
                    row_offset=0)
    _assert_tree_equal(got, ref)


def test_service_append_async_matches_append(key):
    from repro.serve.engine import SketchService
    A, B = _pair()
    ref_svc = SketchService(k=8, probes=4)
    ref_sid = ref_svc.open_stream(key, _D, _NA, _NB)
    got_svc = SketchService(k=8, probes=4)
    got_sid = got_svc.open_stream(key, _D, _NA, _NB)
    for off in range(0, _D, 32):
        ref_svc.append(ref_sid, A[off:off + 32], B[off:off + 32])
    n = got_svc.append_async(
        got_sid, ((A[off:off + 32], B[off:off + 32])
                  for off in range(0, _D, 32)))
    assert n == _D
    _assert_tree_equal(got_svc._streams[got_sid].state,
                       ref_svc._streams[ref_sid].state)


# ---------------------------------------------------------------------------
# compression laws


@settings(deadline=None, max_examples=8)
@given(cosketch=st.sampled_from([0, 4]),
       decay=st.sampled_from([1.0, 0.95]),
       method=st.sampled_from(["gaussian", "srht"]))
def test_f32_round_trip_is_bit_identical(cosketch, decay, method):
    _, st = _stream_state(cosketch=cosketch, decay=decay, method=method)
    settled = streaming._settle_state(st)
    back = decompress_state(compress_state(st, "f32"))
    _assert_tree_equal(back, settled)


@settings(deadline=None, max_examples=6)
@given(spec=st.sampled_from(["f32", "bf16", "int8"]),
       cosketch=st.sampled_from([0, 4]))
def test_norm_and_probe_blocks_bit_exact_at_every_precision(spec, cosketch):
    _, st = _stream_state(cosketch=cosketch)
    back = decompress_state(compress_state(st, spec))
    np.testing.assert_array_equal(np.asarray(back.na2), np.asarray(st.na2))
    np.testing.assert_array_equal(np.asarray(back.nb2), np.asarray(st.nb2))
    np.testing.assert_array_equal(np.asarray(back.probe_acc),
                                  np.asarray(st.probe_acc))
    # key-derived randomness is regenerated, not shipped
    np.testing.assert_array_equal(np.asarray(back.omega),
                                  np.asarray(st.omega))
    assert int(back.rows_seen) == int(st.rows_seen)


@settings(deadline=None, max_examples=6)
@given(spec=st.sampled_from(["f32", "bf16", "int8"]))
def test_wire_pack_round_trips_every_leaf(spec):
    _, st = _stream_state(cosketch=4, decay=0.95)
    comp = compress_state(st, spec)
    back = wire_unpack(wire_pack(comp))
    _assert_tree_equal(back, comp)
    assert wire_bytes(back) == wire_bytes(comp)


def test_wire_bytes_ordering_and_spec_bits():
    _, st = _stream_state(cosketch=4)
    sizes = {s: wire_bytes(compress_state(st, s))
             for s in streaming.WIRE_DTYPES}
    assert sizes["f32"] > sizes["bf16"] > sizes["int8"]
    assert WireSpec("f32").bits == 32 and WireSpec("int8").bits == 8
    with pytest.raises(ValueError):
        compress_state(st, "f16")


@settings(deadline=None, max_examples=6)
@given(spec=st.sampled_from(["bf16", "int8"]),
       split=st.sampled_from([32, 48, 64]))
def test_quantized_merge_error_within_probe_bound(spec, split):
    """Merging two quantized-wire partials stays within the sum of their
    probe-measured wire errors (each round-trip adds its own measured
    error; merge is linear)."""
    summ = StreamingSummarizer(8, probes=4)
    A, B = _pair()
    parts, errs = [], []
    for lo, hi in ((0, split), (split, _D)):
        st = summ.init(_KEY, (_D, _NA, _NB))
        st = summ.update(st, A[lo:hi], B[lo:hi], lo)
        errs.append(wire_error(st, spec))
        parts.append(decompress_state(compress_state(st, spec)))
    merged = tree_merge(parts)

    exact = summ.init(_KEY, (_D, _NA, _NB))
    exact = summ.update(exact, A, B, 0)

    # measure the merged deviation the same way wire_error does: through
    # the probe sketches, normalized by the exact probe norms
    w = np.asarray(exact.omega)
    dev = (np.asarray(merged.A_acc).T @ (np.asarray(merged.B_acc) @ w)
           - np.asarray(exact.A_acc).T @ (np.asarray(exact.B_acc) @ w))
    ref = np.asarray(exact.probe_acc)
    rel = np.sqrt((dev ** 2).sum() / (ref ** 2).sum())
    assert rel <= 2.0 * (sum(errs) + 1e-6), (spec, rel, errs)


def test_wire_error_f32_is_zero_and_gate_is_total():
    _, st = _stream_state()
    assert wire_error(st, "f32") == 0.0
    spec, err = choose_wire_spec(st, tol=0.05)
    assert spec.sketch in streaming.WIRE_DTYPES and err <= 0.05
    # a tolerance no lossy spec can meet lands on lossless f32
    spec, err = choose_wire_spec(st, tol=1e-12)
    assert spec == WireSpec("f32") and err == 0.0
    # the quantized-only candidate list still falls back to f32
    spec, err = choose_wire_spec(st, tol=1e-12, specs=("int8", "bf16"))
    assert spec == WireSpec("f32") and err == 0.0
    with pytest.raises(ValueError):
        choose_wire_spec(st, tol=0.0)
    # no probes -> the gate has nothing to measure
    summ = StreamingSummarizer(8)
    bare = summ.init(_KEY, (_D, _NA, _NB))
    with pytest.raises(ValueError):
        wire_error(bare, "bf16")


def test_compress_requires_key():
    _, st = _stream_state()
    with pytest.raises(ValueError, match="key"):
        compress_state(st._replace(key=None), "f32")


# ---------------------------------------------------------------------------
# compressed checkpoints


@settings(deadline=None, max_examples=4)
@given(spec=st.sampled_from(["f32", "bf16"]))
def test_compressed_checkpoint_round_trip(spec):
    import tempfile
    summ, st = _stream_state(cosketch=4, decay=0.95)
    with tempfile.TemporaryDirectory() as d:
        checkpoint.save_stream_state(d, 3, st, wire=spec)
        man = checkpoint.read_manifest(d)
        assert man["extra"]["wire"]["spec"] == spec
        assert man["extra"]["wire"]["bytes"] == wire_bytes(
            compress_state(st, spec))
        back = checkpoint.restore_stream_state(
            d, summ.init(_KEY, (_D, _NA, _NB)))
        if spec == "f32":
            _assert_tree_equal(back, streaming._settle_state(st))
        else:
            np.testing.assert_array_equal(np.asarray(back.na2),
                                          np.asarray(st.na2))


def test_gated_checkpoint_records_measured_error():
    import tempfile
    summ, st = _stream_state()
    with tempfile.TemporaryDirectory() as d:
        checkpoint.save_stream_state(d, 1, st, tol=0.05)
        wire = checkpoint.read_manifest(d)["extra"]["wire"]
        assert wire["spec"] in streaming.WIRE_DTYPES
        assert 0.0 <= wire["error"] <= 0.05
        back = checkpoint.restore_stream_state(
            d, summ.init(_KEY, (_D, _NA, _NB)))
        assert int(back.rows_seen) == _D


def test_plain_checkpoint_path_unchanged():
    import tempfile
    summ, st = _stream_state(cosketch=4)
    with tempfile.TemporaryDirectory() as d:
        checkpoint.save_stream_state(d, 1, st)
        assert "wire" not in checkpoint.read_manifest(d)["extra"]
        back = checkpoint.restore_stream_state(
            d, summ.init(_KEY, (_D, _NA, _NB)))
        _assert_tree_equal(back, st)
