"""A checkout-shaped directory holding the benchmark's cells at a size the
CPU runs in seconds, for the tests of bench/."""
from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
for _path in (os.path.join(REPO, "src"), BENCH):
    if _path not in sys.path:
        sys.path.insert(0, _path)

TINY_SIZES = {"n1": 128, "n2": 128, "k": 64, "r": 4, "probes": 8,
              "chunk_rows": 256, "samples_m": 24843}
TINY_TRAFFIC = {"pool": 2}


def tiny_root(tmp_path) -> str:
    """BENCHMARK.json and bench/ copied, with every configuration and cell
    cut to ``TINY_SIZES`` and ``TINY_TRAFFIC``."""
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for sub in ("configs", "workloads"):
        os.makedirs(os.path.join(root, "bench", sub))
        for name in os.listdir(os.path.join(BENCH, sub)):
            with open(os.path.join(BENCH, sub, name)) as f:
                doc = json.load(f)
            if sub == "configs":
                doc["sizes"].update(TINY_SIZES)
            else:
                doc["traffic"].update({k: v for k, v in TINY_TRAFFIC.items()
                                       if k in doc["traffic"]})
            with open(os.path.join(root, "bench", sub, name), "w") as f:
                json.dump(doc, f)
    shutil.copy(os.path.join(BENCH, "peaks.json"),
                os.path.join(root, "bench"))
    return root


def fake_chip(chips: int) -> dict:
    """Stands in for the harness's look for a TPU in CPU tests."""
    return {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}
