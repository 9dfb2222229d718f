"""The program's span recorder (``repro.telemetry``): per-name totals, parent
links across nesting, the ring's wrap and its count of what it overwrote,
two threads writing at once, and that the spans land in a profiler trace on
the clock the recorder's own record is aligned to."""
import glob
import os
import sys
import threading
import time

import jax
import pytest

from repro import telemetry
from repro.core.streaming import StreamingSummarizer


def _names(occ):
    return [o.name for o in occ]


def test_totals_add_up_over_occurrences():
    rec = telemetry.Recorder(capacity=16)
    for pause in (0.0, 0.002, 0.001):
        with rec.span("repro.t.a"):
            time.sleep(pause)
    with rec.span("repro.t.b"):
        sum(range(20000))
    snap = rec.snapshot()
    assert set(snap) == {"repro.t.a", "repro.t.b"}
    occ = [o for o in rec.occurrences() if o.name == "repro.t.a"]
    a = snap["repro.t.a"]
    assert a["count"] == 3
    assert a["wall_ns"] == sum(o.end_ns - o.start_ns for o in occ)
    assert a["cpu_ns"] == sum(o.cpu_ns for o in occ)
    assert a["max_wall_ns"] == max(o.end_ns - o.start_ns for o in occ)
    assert a["max_wall_ns"] >= 2_000_000        # the 2 ms sleep
    assert a["cpu_ns"] < a["wall_ns"]           # sleeping takes no CPU
    assert snap["repro.t.b"]["count"] == 1
    assert snap["repro.t.b"]["cpu_ns"] > 0


def test_parents_link_across_nesting():
    rec = telemetry.Recorder(capacity=16)
    with rec.span("repro.t.outer"):
        with rec.span("repro.t.mid"):
            with rec.span("repro.t.leaf"):
                pass
            with rec.span("repro.t.leaf"):
                pass
        with rec.span("repro.t.mid"):
            pass
    with rec.span("repro.t.alone"):
        pass
    occ = rec.occurrences()
    # in the order they closed: a parent follows its children
    assert _names(occ) == ["repro.t.leaf", "repro.t.leaf", "repro.t.mid",
                           "repro.t.mid", "repro.t.outer", "repro.t.alone"]
    assert [o.parent for o in occ] == [2, 2, 4, 4, None, None]
    assert [o.children for o in occ] == [0, 0, 2, 0, 2, 0]
    for o in occ:
        if o.parent is not None:
            p = occ[o.parent]
            assert p.start_ns <= o.start_ns <= o.end_ns <= p.end_ns


def test_a_span_that_raises_is_recorded_and_the_error_passes():
    rec = telemetry.Recorder(capacity=4)
    with pytest.raises(KeyError):
        with rec.span("repro.t.outer"):
            with rec.span("repro.t.fails"):
                raise KeyError("x")
    assert _names(rec.occurrences()) == ["repro.t.fails", "repro.t.outer"]
    with rec.span("repro.t.after"):              # the stack unwound
        pass
    assert rec.occurrences()[-1].parent is None


def test_ring_wraps_and_counts_what_it_overwrote():
    rec = telemetry.Recorder(capacity=4)
    with rec.span("repro.t.call"):
        for _ in range(5):
            with rec.span("repro.t.child"):
                pass
    occ = rec.occurrences()
    assert len(occ) == 4
    assert rec.overwritten() == 2
    assert _names(occ) == ["repro.t.child"] * 3 + ["repro.t.call"]
    # the call counted five children; the ring holds three of them
    assert occ[-1].children == 5
    assert sum(o.parent == 3 for o in occ) == 3
    # the totals lose nothing
    assert rec.snapshot()["repro.t.child"]["count"] == 5
    with pytest.raises(ValueError):
        telemetry.Recorder(capacity=0)


def test_two_threads_keep_their_own_parents():
    rec = telemetry.Recorder(capacity=4096)
    rounds = 300
    errors = []

    def work(tag):
        try:
            for _ in range(rounds):
                with rec.span(f"repro.t.{tag}"):
                    with rec.span(f"repro.t.{tag}.child"):
                        pass
        except Exception as exc:     # noqa: BLE001 - reported by the test
            errors.append(exc)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(tag,))
                   for tag in ("a", "b")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    occ = rec.occurrences()
    assert len(occ) == 4 * rounds and rec.overwritten() == 0
    for o in occ:
        if o.name.endswith(".child"):
            assert occ[o.parent].name == o.name[:-len(".child")]
        else:
            assert o.parent is None and o.children == 1
    snap = rec.snapshot()
    assert {n: s["count"] for n, s in snap.items()} == {
        "repro.t.a": rounds, "repro.t.a.child": rounds,
        "repro.t.b": rounds, "repro.t.b.child": rounds}


def test_named_counts_add_up_apart_from_the_spans():
    rec = telemetry.Recorder(capacity=4)
    for name in ("repro.t.hits", "repro.t.other", "repro.t.hits"):
        rec.count(name)
    assert rec.counts() == {"repro.t.hits": 2, "repro.t.other": 1}
    assert rec.snapshot() == {} and rec.occurrences() == []
    before = telemetry.counts().get("repro.t.module", 0)
    telemetry.count("repro.t.module")
    assert telemetry.counts()["repro.t.module"] == before + 1


def test_module_functions_read_the_process_record():
    before = telemetry.snapshot().get("repro.t.module", {"count": 0})
    with telemetry.span("repro.t.module"):
        pass
    assert telemetry.snapshot()["repro.t.module"]["count"] == \
        before["count"] + 1
    assert telemetry.occurrences()[-1].name == "repro.t.module"
    assert telemetry.RECORDER.capacity == telemetry.CAPACITY
    assert telemetry.overwritten() >= 0


def test_spans_share_the_profilers_clock(tmp_path):
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (96, 6))
    B = jax.random.normal(jax.random.fold_in(key, 1), (96, 4))
    summ = StreamingSummarizer(k=8)
    state = summ.init(key, (96, 6, 4))
    chunks = [(A[i:i + 32], B[i:i + 32]) for i in range(0, 96, 32)]
    jax.block_until_ready(summ.ingest(state, chunks))      # compile first
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(summ.ingest(state, chunks))
    occ = telemetry.occurrences()
    call = max(i for i, o in enumerate(occ) if o.name == "repro.ingest")
    recorded = sorted(o.start_ns for o in occ
                      if o.parent == call and o.name == "repro.ingest.update")

    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(e.start_ns)
    traced_call, = events["repro.ingest"]
    traced = sorted(events["repro.ingest.update"])
    assert len(traced) == len(recorded) == 3
    offset = traced_call - occ[call].start_ns
    for t, r in zip(traced, recorded):
        assert abs(t - (r + offset)) < 1e6
