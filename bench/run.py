#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of BENCHMARK.json.

    python3 bench/run.py --workload stream4k.ingest --seed 7 --seconds 10 --trace 0

Loads the cell's files by name, makes its data on the device from the
seed, warms up (set-up), measures for ``--seconds``, checks what the
measured window produced against the plain reference, and prints the
result as the last line of standard output. Earlier lines name the device
and report set-up, compile-cache hits and misses, and the compilations
counted inside the window. ``--trace 1`` traces the window and reports the
cell's per-layer metrics instead of its end-to-end ones. Without a TPU, or
with fewer chips than the cell needs, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    return harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
