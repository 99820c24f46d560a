"""Each cell's control -- the plain reference in bfloat16 put in the
program's place -- fails the cell's limits, here at a size a test run
holds (the chip runs the same at the cell's own size: chipbench/control.py)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_steer import CELLS, with_pending  # noqa: E402
from chipbench import compare, control, harness  # noqa: E402


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limits(name, monkeypatch):
    with_pending(monkeypatch)
    cell = harness.load_cell(name)
    cell.config = dict(cell.config, grid_side=40)
    for seed in (1, 2, 2**31 + 5):
        got = control.readings(cell, seed)
        assert got["missing"] == 0.0
        assert compare.verdict(got, cell.limits) is False, got
