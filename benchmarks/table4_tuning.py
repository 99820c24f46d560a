"""Table 4 analogue: scheduling policies and yield-threshold sweeps (BC/Us).

A: scheduling policy {random, max_ops, fifo, priority} with yielding on.
B: yield heuristic 1 sweep {0.25μ, 0.5μ, μ, 2μ, 4μ, ∞}.
C: yield heuristic 2 sweep {0.25Δ, 0.5Δ, Δ, 2Δ, 4Δ, ∞}.
D: planner block-size autotune (the knob every sweep above sits on top of).

All sweeps run through the session front door via the reusable measurement
unit ``repro.fpp.planner.measure_run`` — the same code path the planner's
``tune=True`` uses, so what this table measures is what the system ships.
"""
from __future__ import annotations

from benchmarks.common import rnd, sources_for
from repro.core.yielding import YieldConfig, default_delta
from repro.fpp import FPPSession
from repro.fpp.planner import autotune_block_size, measure_run
from repro.graphs.generators import build_suite


def _record(rows, sweep, setting, row):
    rows.append({"sweep": sweep, "setting": setting,
                 "runtime_s": rnd(row["runtime_s"]),
                 "visits": row["visits"],
                 "edges_per_q": rnd(row["edges_per_q"], 0)})


def run(quick: bool = True):
    g = build_suite("road-ca" if quick else "road-us")
    nq = 16 if quick else 100
    srcs = sources_for(g, nq, seed=7)
    sess = FPPSession(g).plan(num_queries=nq, block_size=256, method="bfs")
    bg, _ = sess.prepared()
    delta = default_delta(bg.weight_max)
    rows = []
    # A: policies (yielding enabled, Δ)
    for policy in ("random", "max_ops", "fifo", "priority"):
        row = measure_run(sess, "sssp", srcs, schedule=policy,
                          yield_config=YieldConfig(delta=delta))
        _record(rows, "A:policy", policy, row)
    # B: heuristic 1 (edge budget)
    for mf in (0.25, 0.5, 1.0, 2.0, 4.0, None):
        label = f"{mf}mu" if mf else "no_yield"
        row = measure_run(sess, "sssp", srcs,
                          yield_config=YieldConfig(mu_factor=mf))
        _record(rows, "B:mu", label, row)
    # C: heuristic 2 (Δ window)
    for df in (0.25, 0.5, 1.0, 2.0, 4.0, None):
        label = f"{df}delta" if df else "no_yield"
        yc = YieldConfig(delta=None if df is None else df * delta)
        row = measure_run(sess, "sssp", srcs, yield_config=yc)
        _record(rows, "C:delta", label, row)
    # D: block-size autotune (planner objective: modeled traffic)
    best, tune_rows = autotune_block_size(
        sess, "sssp", srcs[: min(8, len(srcs))], sess.mem,
        candidates=(128, 256, 512) if quick else (64, 128, 256, 512, 1024))
    for row in tune_rows:
        label = f"B={row['block_size']}" + \
            (" <- picked" if row["block_size"] == best else "")
        _record(rows, "D:block", label, row)
    return rows


COLUMNS = ["sweep", "setting", "runtime_s", "visits", "edges_per_q"]
