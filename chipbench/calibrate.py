#!/usr/bin/env python3
"""Runs of one cell with a configuration key swept, to fix the numbers
its configuration records.

    python chipbench/calibrate.py --workload road-sssp-batch \
        --vary grid_side=256,288,320 --seed 11 --seconds 51
    python chipbench/calibrate.py --workload road-ppr-serve-uniform \
        --vary offered_per_s=2,16,32,48 --fix capacity=8 --seed 11 \
        --seconds 51

Each point is one run of the cell through ``harness.run``, the path the
benchmark times, with the configuration's keys overridden; it prints the
run's result line under the values it was run with.  It needs an
accelerator and refuses to run without one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402


def _pair(text: str):
    key, value = text.split("=", 1)
    return key, json.loads(value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--vary", required=True, help="key=v1,v2,...")
    ap.add_argument("--fix", action="append", default=[], help="key=value")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    key, values = args.vary.split("=", 1)
    fixed = dict(_pair(f) for f in args.fix)

    def log(msg):
        print(f"[calibrate] {msg}", file=sys.stderr, flush=True)

    cell = harness.load_cell(args.workload)
    try:
        devs = harness.devices(cell.chips)
    except harness.NoAccelerator as e:
        log(f"no result: {e}")
        return harness.NO_ACCELERATOR
    harness.enable_compile_cache()
    for value in (json.loads(v) for v in values.split(",")):
        t_start = time.perf_counter()
        cell = harness.load_cell(args.workload)
        point = dict(fixed, **{key: value})
        cell.config = dict(cell.config, **point)
        out = harness.run(cell, args.seed, args.seconds, False, devs,
                          t_start, log)
        print(json.dumps(dict(point, result=out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
