"""The trace reduction (bench/devtrace.py), on a small trace recorded on a
v5e chip (tests/bench/data/tiny.xplane.pb: three rounds of a sketch_fused
kernel and a jitted reduction, inside ``bench.update`` / ``bench.poll``
host spans) and on hand-made events."""
import os
import shutil

import pytest

import benchtiny  # noqa: F401  (puts bench/ on sys.path)
import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    shutil.copy(os.path.join(DATA, "tiny.xplane.pb"),
                os.path.join(d, "tiny.xplane.pb"))
    return devtrace.extract(str(d))


def test_extract_reads_device_ops_programs_and_host_spans(tiny):
    assert list(tiny.ops) == ["/device:TPU:0"]
    programs = [name for name, _, _ in tiny.programs["/device:TPU:0"]]
    assert programs == ["jit_sketch_fused", "jit__lambda"] * 3
    ops = {name for name, _, _ in tiny.ops["/device:TPU:0"]}
    assert "kernel:sketch_fused" in ops
    assert [name for name, _, _ in tiny.spans] == \
        ["bench.update", "bench.poll"] * 3


def test_recorded_trace_busy_and_idle_add_up(tiny):
    lo = tiny.spans[0][1]
    hi = tiny.spans[-1][1] + tiny.spans[-1][2]
    busy = devtrace.busy_ns(tiny.ops["/device:TPU:0"], lo, hi)
    gaps = devtrace.idle_gaps(tiny.ops["/device:TPU:0"], lo, hi)
    assert 0 < busy < hi - lo
    assert busy + sum(e - s for s, e in gaps) == pytest.approx(hi - lo)
    kernel = devtrace.time_by_name(tiny.ops["/device:TPU:0"], lo, hi)[
        "kernel:sketch_fused"]
    assert 0 < kernel < busy / 1e9


def test_op_name_shortens_hlo_text():
    assert devtrace.op_name("%add.12 = f32[4]{0} add(f32[4] %x)") == "add"
    assert devtrace.op_name(
        '%sketch_fused.1 = (f32[8]) custom-call(f32[8] %a), '
        'custom_call_target="tpu_custom_call"') == "kernel:sketch_fused"
    assert devtrace.op_name("jit_pipeline_fn(16535464162535096518)") == \
        "jit_pipeline_fn"


def test_union_merges_overlaps_and_clips_to_the_window():
    events = [("a", 0, 10), ("b", 5, 10), ("c", 30, 10), ("d", 100, 5)]
    assert devtrace.union(events) == [(0, 15), (30, 40), (100, 105)]
    assert devtrace.busy_ns(events, 10, 35) == 5 + 5
    assert devtrace.idle_gaps(events, 10, 35) == [(15, 30)]
    assert devtrace.idle_gaps(events, -5, 50) == [(-5, 0), (15, 30),
                                                  (40, 50)]


def test_busy_is_averaged_over_chips():
    trace = devtrace.Trace(
        ops={"/device:TPU:0": [("x", 0, 100)],
             "/device:TPU:1": [("x", 0, 50)]},
        programs={}, spans=[])
    assert devtrace.device_busy_s(trace, 0, 100) == pytest.approx(75e-9)
    assert devtrace.idle_percent(trace, 0, 100) == pytest.approx(25.0)
    assert devtrace.idle_percent(devtrace.Trace({}, {}, []), 0, 100) is None


def test_breakdown_names_gaps_by_the_innermost_host_span():
    trace = devtrace.Trace(
        ops={"/device:TPU:0": [("dot", 0, 40), ("add", 60, 10),
                               ("dot", 90, 10)]},
        programs={},
        spans=[("bench.window", 0, 100), ("bench.ingest", 0, 100),
               ("bench.feed", 40, 20), ("bench.block", 70, 30)])
    out = devtrace.breakdown(trace, 0, 100)
    assert out["device_ops"] == [["dot", 50e-9], ["add", 10e-9]]
    assert out["idle_gaps"] == [["bench.feed", 20e-9],
                                ["bench.block", 20e-9]]


def test_self_time_leaves_out_nested_ops():
    events = [("while", 0, 100), ("fusion", 10, 30), ("dot", 50, 20),
              ("add", 120, 10)]
    assert devtrace.self_time_by_name(events, 0, 200) == pytest.approx(
        {"while": 50e-9, "fusion": 30e-9, "dot": 20e-9, "add": 10e-9})
    assert devtrace.busy_ns(events, 0, 200) == 110


def test_window_of_needs_the_window_span():
    trace = devtrace.Trace({}, {}, [("bench.window", 5, 10)])
    assert devtrace.window_of(trace) == (5, 15)
    with pytest.raises(KeyError):
        devtrace.window_of(devtrace.Trace({}, {}, []))
