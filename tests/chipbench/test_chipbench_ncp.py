"""The NCP cells: their two readers on hand-built spans, the driver's plain
sweep against the program's, and faults planted in the timed path.

Each fault is planted underneath the harness once set-up is done, and the
run must come out ``correct: false``: a step that returns its state
unchanged; a PPR row altered where the session produces it, which the
push invariant (``ppr_mass_gap``) catches; and a profile altered where
``FPPSession.ncp`` returns it, which the driver's whole-batch check
counts as failed seeds."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench_steer import BENCH, REPO, run_cell, steer  # noqa: E402
from chipbench import graphs, harness, spans  # noqa: E402
from chipbench.spans import Span, Spans  # noqa: E402
from chipbench.traffic import ncp  # noqa: E402

CELLS = [w["name"] for w in BENCH["workloads"]
         if json.loads((REPO / "chipbench/cells" / f"{w['name']}.json")
                       .read_text())["driver"] == "ncp"]


def read(name):
    return harness.metric_reader(name).read(None)


def _window():
    # the window is [100, 1100) ns; two runs, the second without the count
    return Spans(window=(100, 1100), spans=[
        Span(spans.RUN, 50, 600, {"visits": 10, "queries": 8,
                                  "lane_visits": 20}),
        Span("fpp.sweep", 600, 700, {"seeds": 8, "support": 40}),
        Span(spans.RUN, 700, 1000, {"visits": 5, "queries": 8}),
        Span("fpp.sweep", 1050, 1150, {"seeds": 8, "support": 30}),
    ])


def test_lane_occupancy_reads_the_runs_that_count_lanes(monkeypatch):
    monkeypatch.setattr(spans, "recorded", _window)
    assert read("lane_occupancy.batch") == pytest.approx(100 * 20 / 80)


def test_sweep_share_is_the_union_of_the_sweep_spans(monkeypatch):
    monkeypatch.setattr(spans, "recorded", _window)
    # 100 ns, then 50 ns clipped to the window, of 1000
    assert read("sweep_share.batch") == pytest.approx(15.0)


@pytest.mark.parametrize("name", ["lane_occupancy.batch",
                                  "sweep_share.batch"])
def test_a_program_without_the_counts_reads_nothing(name, monkeypatch):
    monkeypatch.setattr(spans, "recorded", lambda: Spans(
        window=(0, 100), spans=[Span(spans.RUN, 0, 50,
                                     {"visits": 3, "queries": 4})]))
    assert read(name) is None
    monkeypatch.setattr(spans, "recorded", lambda: None)
    assert read(name) is None


@pytest.mark.parametrize("side", [6, 11])
def test_plain_sweep_equals_the_programs_profile(side):
    """The driver's whole-graph sweep, on the benchmark's edge list, gives
    the program's support-local profile bit for bit, tied scores and
    empty and one-vertex supports included."""
    from repro.core.applications import ncp_profile
    edges = graphs.grid2d(side, np.random.default_rng([side, 0]))
    rng = np.random.default_rng(side)
    pvals = np.zeros((6, edges.n), np.float32)
    for row, k in zip(pvals, [0, 1, 2, 9, edges.n // 2, edges.n]):
        at = rng.choice(edges.n, k, replace=False)
        row[at] = (rng.choice([0.5, 0.25], k) if k % 2
                   else rng.random(k))
    got = ncp_profile(graphs.program_graph(edges), pvals)
    want = ncp.reference_profile(edges, pvals)
    assert np.isfinite(want).any()
    np.testing.assert_array_equal(got, want)


def _wrap(monkeypatch, method, alter):
    from repro.fpp.session import FPPSession
    inner = getattr(FPPSession, method)

    def broken(self, *a, **kw):
        out = inner(self, *a, **kw)
        alter(out)
        return out
    monkeypatch.setattr(FPPSession, method, broken)


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


def ppr_row_altered(monkeypatch):
    def alter(res):
        v = res.values      # each lane's support (delivery="support")
        for lo, hi in zip(v.indptr[:-1], v.indptr[1:]):
            if hi > lo:
                v.data[lo + int(np.argmax(v.data[lo:hi]))] += 1e-3
    _wrap(monkeypatch, "run", alter)


def profile_altered(monkeypatch):
    def alter(out):
        profile = out[0]
        profile[np.flatnonzero(np.isfinite(profile))[-1]] *= 0.5
    _wrap(monkeypatch, "ncp", alter)


FAULTS = {"state_unchanged": (state_unchanged, "ppr_gap"),
          "ppr_row_altered": (ppr_row_altered, "ppr_mass_gap"),
          "profile_altered": (profile_altered, None)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_comes_out_not_correct(name, fault, monkeypatch, tmp_path,
                                     capsys):
    steer(monkeypatch, tmp_path)
    plant, check = FAULTS[fault]
    prepare = ncp.prepare

    def prepare_then_break(cell, data, seed):
        system = prepare(cell, data, seed)
        plant(monkeypatch)
        return system
    monkeypatch.setattr(ncp, "prepare", prepare_then_break)
    res, err = run_cell(name, False, capsys, seconds=0.3)
    assert res["correct"] is False, err
    if check is None:
        assert res["failed"] > 0
        assert "differs from the plain sweep" in err
    else:
        c = res["checks"][check]
        assert c["value"] > c["limit"], err


@pytest.mark.parametrize("name", CELLS)
def test_a_program_without_support_delivery_stops_at_set_up(name,
                                                            monkeypatch):
    """The configuration's support delivery is part of the deployment: a
    program whose ``FPPSession.run`` lacks it cannot run the cell, and the
    driver says so before it plans anything."""
    from repro.fpp.session import FPPSession
    cell = harness.load_cell(name)
    assert cell.config["delivery"] == "support"

    def run(self, kind, sources, *, alpha=0.15, eps=1e-4, **kw):
        raise AssertionError("set-up went on")
    monkeypatch.setattr(FPPSession, "run", run)
    with pytest.raises(SystemExit, match="delivery='support'"):
        ncp.prepare(cell, None, 0)
