"""BENCHMARK.json and the files the harness finds by name."""
import json
import os
import re

import pytest

import benchtiny
import loader

ROOT = benchtiny.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_keys_and_names_keep_the_contract(benchmark):
    assert set(benchmark) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert 1 <= benchmark["run_seconds"] <= 51
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in benchmark[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for metric in benchmark["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in benchmark["end_to_end"])
    for path in benchmark["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))


def test_every_cell_has_its_files_and_reports_what_it_must(benchmark):
    for entry in benchmark["workloads"]:
        cell = loader.load_cell(entry["name"], ROOT)
        loader.load_module("traffic", cell.traffic["kind"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for metric in cell.per_layer:
            assert metric["moves"] in e2e
            assert callable(loader.load_module("metrics", metric["name"]).read)


def test_config_files_are_their_configs(benchmark):
    for entry in benchmark["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        assert config["name"] == entry["name"]
        assert config["reduced"] == entry["reduced"]


def test_metrics_of_follows_workloads_then_moves():
    bench = {"end_to_end": [
        {"name": "rate", "workloads": ["a"]},
        {"name": "tail", "workloads": ["b"]},
        {"name": "setup_s"}],
        "per_layer": [
        {"name": "x", "moves": "rate", "workloads": ["a"]},
        {"name": "y", "moves": "tail"},
        {"name": "z", "moves": "setup_s", "workloads": ["b"]}]}
    e2e, per_layer = loader.metrics_of(bench, "a")
    assert [m["name"] for m in e2e] == ["rate", "setup_s"]
    assert [m["name"] for m in per_layer] == ["x"]
    e2e, per_layer = loader.metrics_of(bench, "b")
    assert [m["name"] for m in e2e] == ["tail", "setup_s"]
    assert [m["name"] for m in per_layer] == ["y", "z"]


def test_unknown_cell_device_and_file_are_errors():
    with pytest.raises(KeyError):
        loader.load_cell("no.such.cell", ROOT)
    with pytest.raises(KeyError):
        loader.load_peaks("TPU v99", ROOT)
    with pytest.raises(FileNotFoundError):
        loader.load_module("metrics", "no.such.metric")
    assert loader.load_peaks("TPU v5 lite", ROOT)["hbm_bytes_per_s"] == 819e9
