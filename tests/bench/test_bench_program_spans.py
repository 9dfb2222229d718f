"""The readers of the program's own spans (bench/program_spans.py,
``ingest.host_ms_per_chunk``, ``ingest.launch_idle_share``), on a recorder
filled by a stand-in ingest call and a hand-made trace around it."""
import sys
import time

import pytest

import benchtiny  # noqa: F401  (puts bench/ and src/ on sys.path)
import devtrace
import harness
import loader
import program_spans
from repro import telemetry

CHUNKS = 3
OFFSET = 7_000_000_000          # trace clock minus the recorder's clock
SLACK = 1_000                   # bench.ingest closes 1 us after the call


def _ingest(rec, chunks=CHUNKS):
    """Spans shaped like one ``StreamingSummarizer.ingest`` call."""
    with rec.span("repro.ingest"):
        for _ in range(chunks):
            with rec.span("repro.ingest.feed"):
                pass
            with rec.span("repro.ingest.stage"):
                sum(range(2000))
            with rec.span("repro.ingest.update"):
                time.sleep(0.002)
        with rec.span("repro.ingest.feed"):
            pass


def _read(metric, trace, window, chunks=CHUNKS):
    ctx = harness.MetricContext(None, {"chunks": chunks}, trace, window, {})
    return loader.load_module("metrics", metric).read(ctx)


@pytest.fixture()
def recorded(monkeypatch):
    rec = telemetry.Recorder(capacity=64)
    monkeypatch.setattr(telemetry, "occurrences", rec.occurrences)
    _ingest(rec)
    occ = rec.occurrences()
    call = occ[-1]
    updates = [o for o in occ if o.name == "repro.ingest.update"]
    return rec, call, updates


def _trace(call, updates, ingest_end_slack=SLACK, with_anchor=True):
    """bench.window = bench.ingest around the call; the device is busy
    except until 100 ns before the first launch and for 200 ns inside the
    second."""
    lo = call.start_ns + OFFSET
    hi = call.end_ns + OFFSET + ingest_end_slack
    first = updates[0].start_ns + OFFSET - 100
    gap = updates[1].start_ns + OFFSET + 10
    ops = [("dot", first, gap - first), ("dot", gap + 200, hi - gap - 200)]
    spans = [("bench.window", lo, hi - lo)]
    if with_anchor:
        spans.append(("bench.ingest", lo, hi - lo))
    return devtrace.Trace({"/device:TPU:0": ops}, {}, spans), (lo, hi)


def test_launch_idle_share_counts_only_gaps_inside_launches(recorded):
    _, call, updates = recorded
    trace, (lo, hi) = _trace(call, updates)
    gaps = devtrace.idle_gaps(trace.ops["/device:TPU:0"], lo, hi)
    assert len(gaps) == 2        # one before the first launch, one inside
    got = _read("ingest.launch_idle_share", trace, (lo, hi))
    assert got == pytest.approx(100.0 * 200 / (hi - lo))


def test_host_ms_per_chunk_is_stage_and_update_cpu_per_launch(recorded):
    rec, _, _ = recorded
    occ = rec.occurrences()
    cpu = sum(o.cpu_ns for o in occ
              if o.name in ("repro.ingest.stage", "repro.ingest.update"))
    trace, window = _trace(occ[-1], [o for o in occ
                                     if o.name == "repro.ingest.update"])
    got = _read("ingest.host_ms_per_chunk", trace, window)
    assert got == pytest.approx(cpu / CHUNKS / 1e6)
    assert 0 < got < 2.0         # the 2 ms sleeps take no CPU


def test_anchor_whose_ends_disagree_gives_none(recorded):
    _, call, updates = recorded
    trace, window = _trace(call, updates, ingest_end_slack=SLACK + 1_500_000)
    assert program_spans.offset_ns(
        trace, program_spans.last_call()) is None
    assert _read("ingest.launch_idle_share", trace, window) is None
    trace, window = _trace(call, updates, ingest_end_slack=SLACK + 500_000)
    assert _read("ingest.launch_idle_share", trace, window) is not None


def test_missing_bench_ingest_gives_none(recorded):
    _, call, updates = recorded
    trace, window = _trace(call, updates, with_anchor=False)
    assert _read("ingest.launch_idle_share", trace, window) is None


def test_chunk_count_mismatch_gives_none(recorded):
    _, call, updates = recorded
    trace, window = _trace(call, updates)
    assert _read("ingest.host_ms_per_chunk", trace, window,
                 chunks=CHUNKS + 1) is None
    assert _read("ingest.host_ms_per_chunk", trace, window,
                 chunks=CHUNKS) is not None


def test_overwritten_ring_gives_none(monkeypatch):
    rec = telemetry.Recorder(capacity=8)        # the call closes 11 spans
    monkeypatch.setattr(telemetry, "occurrences", rec.occurrences)
    _ingest(rec)
    assert rec.overwritten() > 0
    occ = rec.occurrences()
    trace, window = _trace(occ[-1], [o for o in occ
                                     if o.name == "repro.ingest.update"][-2:])
    assert program_spans.last_call() is None
    assert _read("ingest.host_ms_per_chunk", trace, window) is None
    assert _read("ingest.launch_idle_share", trace, window) is None


def test_program_without_spans_gives_none(recorded, monkeypatch):
    _, call, updates = recorded
    trace, window = _trace(call, updates)
    import repro                 # a commit before the program's record
    monkeypatch.delattr(repro, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert program_spans.last_call() is None
    assert _read("ingest.host_ms_per_chunk", trace, window) is None
    assert _read("ingest.launch_idle_share", trace, window) is None


def test_no_ingest_call_gives_none(monkeypatch):
    rec = telemetry.Recorder(capacity=8)
    monkeypatch.setattr(telemetry, "occurrences", rec.occurrences)
    with rec.span("repro.ingest.update"):
        pass
    assert program_spans.last_call() is None


@pytest.mark.parametrize("seed", range(4))
def test_overlap_matches_clipping_each_gap(seed):
    import random
    rng = random.Random(seed)

    def intervals(n):
        events = [("e", rng.uniform(0, 1000), rng.uniform(0, 40))
                  for _ in range(n)]
        return devtrace.union(events)

    gaps, launches = intervals(60), intervals(25)
    want = sum(devtrace.busy_ns([("l", s, e - s) for s, e in launches],
                                gs, ge) for gs, ge in gaps)
    assert program_spans.overlap_ns(gaps, launches) == pytest.approx(want)
    assert program_spans.overlap_ns(launches, gaps) == pytest.approx(want)
    assert program_spans.overlap_ns([], launches) == 0
