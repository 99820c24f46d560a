#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference one precision
below the configuration's (float32 -> bfloat16), put in the program's
place, at the cell's own size.

    python chipbench/control.py --workload road-sssp-batch --seeds 1,2,3

For each seed it takes the sources a run of the cell compares from the
cell's traffic driver (``control_sources``), computes the control's answers for them, and prints the
numbers ``compare.py`` reads with the cell's limits: every one of them
has to fail at least one limit.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from chipbench import compare, graphs, harness, reference  # noqa: E402

LOWER = {"float32": "bfloat16", "float64": "float32"}


def readings(cell, seed: int) -> dict:
    """The control's numbers for one seed at ``cell``'s size."""
    kind = cell.params["kind"]
    edges = graphs.make_graph(cell.config, np.random.default_rng([seed, 0]))
    count = int(cell.params.get("check", sum(
        cell.params.get("sample", {}).values())))
    src = cell.driver.control_sources(
        cell, edges.n, seed, float(harness.benchmark()["run_seconds"]), count)
    low = LOWER[cell.config["precision"]]
    if kind == "sssp":
        vals = reference.sssp(edges, src, precision=low)
        res = [None] * len(src)
    else:
        vals, res = reference.ppr(edges, src, float(cell.config["alpha"]),
                                  precision=low)
    answers = [compare.Answer(kind, int(s), v, residual=r)
               for s, v, r in zip(src, vals, res)]
    return compare.numbers(edges, answers,
                           alpha=float(cell.config.get("alpha", 0.15)),
                           eps=float(cell.config.get("eps", 1e-4)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        got = readings(cell, seed)
        fails = compare.verdict(got, cell.limits) is False
        failed_all &= fails
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": got, "limits": cell.limits,
                          "fails": fails,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
