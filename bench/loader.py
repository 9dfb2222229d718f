"""Finds a cell's files by name: nothing here names a cell, a configuration,
a traffic kind or a metric.

    BENCHMARK.json                   which metrics each cell reports
    bench/workloads/<cell>.json      configuration, traffic kind and its
                                     parameters, chips, why
    bench/configs/<config>.json      source, sizes, assumed/reduced, the
                                     guarantees and the correctness limits
    bench/traffic/<kind>.py          the driver of one traffic kind
    bench/metrics/<metric>.py        the reader of one per-layer metric
    bench/work/<name>.py             operation and byte counts of one piece
                                     of work, as functions of its shapes
    bench/peaks.json                 peaks keyed by ``device_kind``

A later cell, configuration or metric is a new file, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List, NamedTuple

#: the checkout's root: the directory that holds BENCHMARK.json and bench/
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


class Cell(NamedTuple):
    """One entry of ``workloads`` with everything its files say."""

    name: str
    chips: int
    config: Dict[str, Any]       # bench/configs/<config>.json
    traffic: Dict[str, Any]      # the cell file's "traffic" object
    end_to_end: List[dict]       # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(benchmark: dict, cell: str) -> tuple:
    """(end_to_end, per_layer) entries of BENCHMARK.json that ``cell``
    reports: a metric with a ``workloads`` list names its cells; a per-layer
    metric without one goes wherever its ``moves`` metric is reported."""
    e2e = [m for m in benchmark["end_to_end"]
           if cell in m.get("workloads", [cell])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in benchmark["per_layer"]
                 if (cell in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return e2e, per_layer


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` as BENCHMARK.json and its own files describe it."""
    bench = os.path.join(root, "bench")
    benchmark = read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in benchmark["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    spec = read_json(os.path.join(bench, "workloads", name + ".json"))
    if spec["config"] != entry["config"] or spec["chips"] != entry["chips"]:
        raise ValueError(f"bench/workloads/{name}.json disagrees with "
                         f"BENCHMARK.json on config or chips")
    config = read_json(os.path.join(bench, "configs",
                                    entry["config"] + ".json"))
    e2e, per_layer = metrics_of(benchmark, name)
    return Cell(name, entry["chips"], config, spec["traffic"], e2e,
                per_layer)


def load_peaks(kind: str, root: str = ROOT) -> dict:
    """The peaks of ``kind`` (JAX's ``device_kind``); a device that is not
    in the table is an error, never a default."""
    table = read_json(os.path.join(root, "bench", "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"bench/peaks.json has no peaks for device kind "
                       f"{kind!r} (it has {sorted(table['devices'])})")
    return table["devices"][kind]
