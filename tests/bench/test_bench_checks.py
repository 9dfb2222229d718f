"""The check that decides ``correct``, at a size the CPU holds: sound runs
pass, the control (the reference in bf16x3 in the program's place) fails,
and a run whose timed path is broken underneath comes out not correct."""
import json

import pytest

import benchtiny
import control
import harness
from repro.core.streaming import StreamingSummarizer

SEEDS = (5, 2 ** 33 + 11)


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.tiny_root(tmp_path_factory.mktemp("root"))


def _limits(root):
    import loader
    return loader.load_cell("stream4k.ingest", root).config["limits"]


@pytest.mark.parametrize("cell", ["stream4k.ingest"])
def test_sound_runs_pass_and_the_control_fails(root, cell, monkeypatch):
    monkeypatch.setattr(harness, "find_devices", benchtiny.fake_chip)
    limits = _limits(root)
    for row in control.readings(cell, SEEDS, 0.5, root=root):
        assert all(v <= limits[k] for k, v in row["program"].items()), row
        assert row["control"]["summary_gap"] > limits["summary_gap"], row


@pytest.mark.parametrize("cell,metric,rate", [
    ("stream4k.ingest", "ingest_rows_per_s",
     lambda c: c["rows"] / c["window_s"])])
def test_end_to_end_metrics_take_all_the_work_and_all_the_window(
        root, cell, metric, rate):
    import loader
    spec = loader.load_cell(cell, root)
    driver = loader.load_module("traffic", spec.traffic["kind"])
    outcome = driver.window(driver.setup(spec, 3), 0.5, harness.span)
    counters = outcome.counters
    assert counters["window_s"] >= 0.5
    assert outcome.end_to_end[metric] == rate(counters)
    assert outcome.attempted == counters["chunks"]


def _correct(root, cell, capsys) -> bool:
    rc = harness.run_cell(cell, SEEDS[0], 0.5, False, root=root,
                          find=benchtiny.fake_chip)
    assert rc == 0
    return json.loads(capsys.readouterr().out.splitlines()[-1])["correct"]


def test_a_sound_run_is_correct(root, capsys):
    assert _correct(root, "stream4k.ingest", capsys)


def test_an_update_that_returns_its_state_unchanged_fails(root, capsys,
                                                          monkeypatch):
    monkeypatch.setattr(StreamingSummarizer, "update",
                        lambda self, state, *args: state)
    assert not _correct(root, "stream4k.ingest", capsys)


def test_half_of_each_chunk_left_out_fails(root, capsys, monkeypatch):
    absorb = StreamingSummarizer._absorb

    def half(self, state, A, B, gids, t, hi1):
        h = t // 2
        return absorb(self, state, A[:h], B[:h], gids[:h], t, hi1)

    monkeypatch.setattr(StreamingSummarizer, "_absorb", half)
    assert not _correct(root, "stream4k.ingest", capsys)


def test_a_sketch_altered_where_it_is_produced_fails(root, capsys,
                                                     monkeypatch):
    finalize = StreamingSummarizer.finalize

    def altered(self, state):
        s = finalize(self, state)
        return s._replace(A_sketch=s.A_sketch.at[0].multiply(1.001))

    monkeypatch.setattr(StreamingSummarizer, "finalize", altered)
    assert not _correct(root, "stream4k.ingest", capsys)
