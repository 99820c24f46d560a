#!/usr/bin/env python3
"""The benchmark's command: one cell of BENCHMARK.json, run once.

    python3 chipbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

See ``chipbench/harness.py``.  The last line of standard output is the
result; the numbers compared with the reference are the last lines of
standard error.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
