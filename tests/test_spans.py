"""The program's spans and counters (``repro.spans``, DESIGN.md §3.5).

A batch run records a two-level span tree under ``fpp.run``, whose counts
agree with ``SessionResult.stats``; the megastep's phases are named in
its op metadata; a served request's spans share its ``rid`` across the
admission, pool-worker and delivery threads.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core.engine import FPPEngine
from repro.core.partition import partition
from repro.fpp import FPPSession
from repro.graphs.generators import grid2d
from repro.serve import GraphRequest, GraphServer

LEAVES = {spans.PREPARE, spans.ENGINE, spans.INIT_STATE, spans.DISPATCH,
          spans.SYNC, spans.HARVEST, spans.FINALIZE}


def recorded(directory):
    """[(name, start, end, line, args)] of the program's host spans."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("fpp.", "serve.")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                (plane.name, i), dict(e.stats)))
    return out


def parents(found, child):
    """The recorded spans that enclose ``child`` on its thread."""
    name, s, t, line, _ = child
    return [p for p in found if p is not child and p[3] == line
            and p[1] <= s and t <= p[2]]


@pytest.fixture(scope="module")
def session():
    # 36 partitions of 16: a 4-source SSSP batch runs several 64-visit
    # chunks
    g = grid2d(24, 24, seed=1)
    return FPPSession(g).plan(num_queries=4, block_size=16)


@pytest.fixture(scope="module")
def traced(session, tmp_path_factory):
    directory = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(directory):
        res = session.run("sssp", np.array([0, 100, 300, 575]))
    return res, recorded(directory)


def test_batch_run_records_the_span_tree_two_levels_deep(traced):
    res, found = traced
    assert {f[0] for f in found} == LEAVES | {spans.RUN}
    root, = [f for f in found if f[0] == spans.RUN]
    assert parents(found, root) == []
    for leaf in found:
        if leaf is not root:
            assert [p[0] for p in parents(found, leaf)] == [spans.RUN]
    assert root[4]["kind"] == "sssp" and root[4]["queries"] == 4
    assert root[4]["backend"] == "engine"


def test_one_sync_span_per_host_sync(traced):
    res, found = traced
    syncs = [f for f in found if f[0] == spans.SYNC]
    assert len(syncs) == res.stats["host_syncs"] > 1
    dispatches = [f for f in found if f[0] == spans.DISPATCH]
    assert [f[4]["chunk"] for f in dispatches] == list(range(len(syncs)))
    root, = [f for f in found if f[0] == spans.RUN]
    assert root[4]["chunks"] == res.stats["host_syncs"]


def test_visit_counts_agree_across_spans_and_stats(traced):
    res, found = traced
    visits = sum(f[4]["visits"] for f in found if f[0] == spans.SYNC)
    assert visits == res.stats["visits"] == res.stats["visit_counts"].sum()
    assert res.stats["visit_counts"].dtype == np.int64
    root, = [f for f in found if f[0] == spans.RUN]
    assert root[4]["visits"] == res.stats["visits"]


def test_chunk_reads_count_every_boundary_transfer(traced):
    res, found = traced
    harvest = sum(f[4]["reads"] for f in found if f[0] == spans.HARVEST)
    syncs = res.stats["host_syncs"]
    assert res.stats["chunk_reads"] == syncs + harvest == 5 * syncs
    root, = [f for f in found if f[0] == spans.RUN]
    assert root[4]["chunk_reads"] == res.stats["chunk_reads"]


def test_megastep_traces_once_per_run(session, traced):
    res, found = traced
    assert res.stats["megastep_traces"] == 1
    traces = [f[4]["traces"] for f in found if f[0] == spans.DISPATCH]
    assert traces[0] == 1 and not any(traces[1:])
    again = session.run("sssp", np.array([1, 2, 3, 4]))
    assert again.stats["megastep_traces"] == 1


def test_engine_span_notes_the_relax_width(traced):
    res, found = traced
    widths = [f[4]["relax_width"] for f in found
              if f[0] == spans.ENGINE and "relax_width" in f[4]]
    # B = 16 is too narrow for the pull-ELL view: the dense tile ran
    assert widths == [res.stats["relax_width"]] == [16]


def test_init_state_span_carries_the_state_bytes(traced):
    _, found = traced
    init, = [f for f in found if f[0] == spans.INIT_STATE]
    # one [P, Q, B] f32 plane and the [P + 1, Q, B] buffer, plus the [P]
    # prio (f32), ops and stamp (i32) planes
    P, Q, B = 36, 4, 16
    assert init[4]["bytes"] == 4 * (P * Q * B + (P + 1) * Q * B + 3 * P)


def test_ncp_sweep_span_follows_the_run_with_its_counts(session, tmp_path):
    seeds = np.array([0, 100, 300, 575])
    with jax.profiler.trace(str(tmp_path)):
        profile, res = session.ncp(seeds)
    found = recorded(str(tmp_path))
    sweep, = [f for f in found if f[0] == spans.SWEEP]
    root, = [f for f in found if f[0] == spans.RUN]
    assert parents(found, sweep) == [] and root[2] <= sweep[1]
    assert sweep[4]["seeds"] == 4
    assert sweep[4]["support"] == np.count_nonzero(res.values > 0)
    assert root[4]["lane_visits"] == res.stats["lane_visits"] > 0


def test_host_loop_counts_visits_per_partition(session):
    bg, perm = session.prepared()
    src = perm[np.array([0, 100, 300, 575])]
    eng = FPPEngine(bg, num_queries=4)
    chunked = eng.run(src).stats
    loop = eng.run(src, host_loop=True).stats
    np.testing.assert_array_equal(chunked.visit_counts, loop.visit_counts)
    assert loop.megastep_traces == 0
    assert loop.chunk_reads == 5 * loop.visits + 3


def test_spans_off_the_profiler_record_nothing():
    with spans.span(spans.SYNC, chunk=0) as sp:
        assert not sp.is_enabled()
        spans.note(sp, visits=1)


@pytest.mark.parametrize("harvest_mask", [False, True],
                         ids=["engine", "streaming"])
def test_megastep_names_the_visit_phases(harvest_mask):
    from repro.core import visit
    bg, _ = partition(grid2d(8, 8, seed=2), 16)
    eng = FPPEngine(bg, num_queries=2)
    step = visit.make_megastep(eng.dg, eng.algebra, eng.max_rounds, K=8,
                               harvest_mask=harvest_mask)
    state = eng.init_state(np.array([0, 5]))
    text = step.lower(state, jnp.int32(0), jnp.int32(8),
                      jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("select", "visit/apply", "visit/relax", "visit/emit",
                  "visit/writeback"):
        assert re.search(rf'/{scope}["/]', text), scope
    # the pending-lane reduction runs only where the harvest asks for it
    assert bool(re.search(r"/harvest_mask/reduce_or", text)) == harvest_mask
    assert step.traces == 1


def test_served_request_spans_share_its_rid(tmp_path):
    g = grid2d(8, 8, seed=3)
    server = GraphServer(capacity=2, k_visits=8, autoscaler=None)
    server.register_graph("g", g, num_queries=2, block_size=16)
    with jax.profiler.trace(str(tmp_path)):
        server.start()
        try:
            rids = [server.submit(GraphRequest(kind="sssp", source=s,
                                               graph="g"))
                    for s in (0, 40)]
            for rid in rids:
                assert server.result(rid, timeout=120).status == "ok"
        finally:
            server.shutdown()
    found = recorded(str(tmp_path))
    for rid in rids:
        mine = {f[0]: f for f in found if f[4].get("rid") == rid}
        assert set(mine) == {spans.SUBMIT, spans.ADMIT, spans.DELIVER}
        assert (mine[spans.SUBMIT][1] <= mine[spans.ADMIT][1]
                <= mine[spans.DELIVER][1])
    lanes = {f[0] for f in found if f[0].startswith("fpp.")}
    assert {spans.DISPATCH, spans.SYNC, spans.HARVEST} <= lanes
