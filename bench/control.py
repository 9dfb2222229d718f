#!/usr/bin/env python3
"""Readings that set a cell's correctness limits: for each seed, one run
of the cell's window, then the program's numbers and the control's.

    python3 bench/control.py --workload stream4k.ingest --seeds 11,12,13 --seconds 10

The control is the plain reference put in the program's place, computed
one precision step below what the configuration states (bf16 in three
passes for f32 at HIGHEST). A limit lies above the largest program reading
over a dozen seeds or more and below the smallest control reading. The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def readings(name: str, seeds, seconds: float, *, root=None) -> list:
    """[{seed, program: {...}, control: {...}}] for each seed, in one
    process (the compile cache and the chip are shared)."""
    import harness
    import loader
    root = root or loader.ROOT
    cell = loader.load_cell(name, root)
    driver = loader.load_module("traffic", cell.traffic["kind"])
    harness.find_devices(cell.chips)
    harness.enable_compile_cache()
    out = []
    for seed in seeds:
        session = driver.setup(cell, seed)
        outcome = driver.window(session, seconds, harness.span)
        row = {"seed": seed, "attempted": outcome.attempted,
               "program": {c.name: c.value for c in
                           driver.check(session, outcome)},
               "control": driver.control(session, outcome)}
        print(json.dumps(row), flush=True)
        out.append(row)
        del session, outcome
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    readings(args.workload, seeds, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
