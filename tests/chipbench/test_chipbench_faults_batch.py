"""The batch cells' comparison fails a broken timed path.

Each fault is planted in the program underneath the harness once set-up
is done, and the run must come out ``correct: false``: a step that
returns its state unchanged, half of each batch left out (its rows keep
their initial values), and one answer altered where it is produced.  The
cells run on one chip, so there is no exchange between chips to leave
out."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_steer import BENCH, run_cell, steer  # noqa: E402
from chipbench.traffic import batch  # noqa: E402

CELLS = [w["name"] for w in BENCH["workloads"]
         if w["traffic"].endswith("-q64")]


def state_unchanged(monkeypatch):
    from repro.core import visit
    make = visit.make_megastep

    def broken(*a, **k):
        inner = make(*a, **k)

        class Stuck:
            def __call__(self, state, *rest):
                _, stats = inner(state, *rest)
                return state, stats
        return Stuck()
    monkeypatch.setattr(visit, "make_megastep", broken)


def _wrap_run(monkeypatch, alter):
    from repro.fpp.session import FPPSession
    run = FPPSession.run

    def broken(self, kind, sources, **kw):
        res = run(self, kind, sources, **kw)
        alter(kind, res)
        return res
    monkeypatch.setattr(FPPSession, "run", broken)


def half_left_out(monkeypatch):
    def alter(kind, res):
        half = len(res.values) // 2
        res.values[half:] = np.inf if kind == "sssp" else 0.0
        res.values[np.arange(half, len(res.values)),
                   res.sources[half:]] = 0.0
    _wrap_run(monkeypatch, alter)


def answer_altered(monkeypatch):
    def alter(kind, res):
        for row in res.values:
            if kind == "sssp":
                far = int(np.argmax(np.where(np.isfinite(row), row, -1)))
                row[far] *= 1.001
            else:
                row[int(np.argmax(row))] += 1e-3
    _wrap_run(monkeypatch, alter)


FAULTS = {"state_unchanged": state_unchanged,
          "half_left_out": half_left_out,
          "answer_altered": answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_comes_out_not_correct(name, fault, monkeypatch, tmp_path,
                                     capsys):
    steer(monkeypatch, tmp_path)
    prepare = batch.prepare

    def prepare_then_break(cell, data, seed):
        system = prepare(cell, data, seed)
        FAULTS[fault](monkeypatch)
        return system
    monkeypatch.setattr(batch, "prepare", prepare_then_break)
    res, err = run_cell(name, False, capsys, seconds=0.3)
    assert res["correct"] is False, err
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
