"""The served cells' comparison fails a broken timed path.

Each fault is planted in the lanes' executor once set-up is done, and
the run must come out ``correct: false``: a chunk that leaves the lane
state as it was (its lanes never finish), half of the lanes' answers
left at their initial values, and one answer altered where the lane
produces it.  One chip: no exchange between chips to leave out."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_steer import BENCH, run_cell, steer  # noqa: E402
from chipbench.traffic import open_loop  # noqa: E402

CELLS = [w["name"] for w in BENCH["workloads"]
         if "serve" in w["name"]]


def state_unchanged(monkeypatch):
    from repro.fpp.streaming import StreamingExecutor
    chunk = StreamingExecutor._chunk

    def broken(self, limit):
        state = self.state
        out = chunk(self, limit)
        self.state = state
        return out
    monkeypatch.setattr(StreamingExecutor, "_chunk", broken)
    # lanes that never finish: the run waits a second past the window,
    # not a minute
    monkeypatch.setattr(open_loop, "GRACE_S", 1.0)


def _wrap_harvest(monkeypatch, alter):
    from repro.fpp.streaming import StreamingExecutor
    harvest = StreamingExecutor._harvest

    def broken(self, pending=None):
        before = set(self.finished)
        harvest(self, pending)
        for qid in self.finished:
            if qid not in before:
                alter(self.queries[qid])
    monkeypatch.setattr(StreamingExecutor, "_harvest", broken)


def half_left_out(monkeypatch):
    def alter(q):
        if q.qid % 2:
            q.values = np.zeros_like(q.values)
    _wrap_harvest(monkeypatch, alter)


def answer_altered(monkeypatch):
    def alter(q):
        q.values = q.values.copy()
        q.values[int(np.argmax(q.values))] += 1e-3
    _wrap_harvest(monkeypatch, alter)


FAULTS = {"state_unchanged": state_unchanged,
          "half_left_out": half_left_out,
          "answer_altered": answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_comes_out_not_correct(name, fault, monkeypatch, tmp_path,
                                     capsys):
    steer(monkeypatch, tmp_path)
    prepare = open_loop.prepare

    def prepare_then_break(cell, data, seed):
        system = prepare(cell, data, seed)
        FAULTS[fault](monkeypatch)
        return system
    monkeypatch.setattr(open_loop, "prepare", prepare_then_break)
    res, err = run_cell(name, False, capsys, seconds=0.5)
    assert res["correct"] is False, err
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
