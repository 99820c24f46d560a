"""Benchmark harness entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick suite
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale-ish
    PYTHONPATH=src python -m benchmarks.run --only table1_profile

Each module's ``run(quick)`` returns rows; results are persisted under
results/bench/<name>.json and summarized here.
"""
from __future__ import annotations

import argparse
import importlib
import time

from benchmarks.common import fmt_table, save_rows

MODULES = [
    "table1_profile",     # Table 1 / Fig 1: parallelism scheme profile
    "fig9_overall",       # Fig 9 / Table 3: BC/LL/NCP overall
    "fig10_work",         # Fig 10: work + traffic vs sequential oracle
    "fig11_ablation",     # Fig 11: cumulative optimizations
    "table4_tuning",      # Table 4: scheduling + yield threshold sweeps
    "fig15_scaling",      # Fig 15: query-count scaling
    "fig16_partition_size",  # Fig 16: partition-size sweep
    "bench_dispatch",     # ISSUE 4: host-loop vs K-visit megastep dispatch
    "bench_serve",        # ISSUE 8: open-loop SLO sweep (continuous batching)
    "bench_kinds",        # ISSUE 10: cc/kreach/rw rows on the ingested fixture
]


def main():
    from repro.launch.persistent_cache import enable_persistent_cache
    enable_persistent_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="module name, or a comma-separated list")
    args = ap.parse_args()
    mods = args.only.split(",") if args.only else MODULES
    failures = []
    for name in mods:
        print(f"\n=== {name} {'(full)' if args.full else '(quick)'} ===",
              flush=True)
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            rows = mod.run(quick=not args.full)
        except Exception as e:                      # noqa: BLE001
            import traceback
            traceback.print_exc()
            failures.append({"module": name,
                             "error": f"{type(e).__name__}: {e}"})
            # persist the failure where the rows would have gone, so
            # results/bench/ reflects partial runs instead of silence
            save_rows(name, [{"module": name, "status": "failed",
                              "error": f"{type(e).__name__}: {e}"}])
            continue
        path = save_rows(name, rows)
        print(fmt_table(rows, mod.COLUMNS))
        print(f"[{time.perf_counter() - t0:6.1f}s] -> {path}")
    # always write _failures.json (empty on success) so results/bench/
    # reflects THIS run's status rather than a stale earlier failure
    save_rows("_failures", failures)
    if failures:
        raise SystemExit("benchmark failures: "
                         + ", ".join(f["module"] for f in failures))
    print("\nAll benchmarks complete.")


if __name__ == "__main__":
    main()
