"""GraphServer: the multi-tenant serving contract (DESIGN.md §4.2).

What serving must never change: answers.  A request served through lane
pools, weighted-fair admission, and chunked megasteps returns values
bit-identical to ``FPPSession.run`` of the same query — for every kind,
because admission only injects source ops a one-shot run would have
started with (the §3.3 exactness argument) and the engine's deterministic
priority schedule makes the visit sequence independent of chunking.

What serving must additionally guarantee, pinned here:
  * a hot tenant at 10x offered load cannot starve another tenant
    (queue-wait bound from start-time fair queueing);
  * deadline-expired requests are rejected with an explicit response,
    never silently dropped;
  * two registered graphs serve interleaved traffic with no state bleed;
  * request priorities plumb through pool arbitration
    (core/scheduler.py ``prefer_older_ties``).
What continuous batching adds, pinned here:
  * multi-threaded submitters against the running lanes get the same
    bit-identical answers (and ``serve_forever`` matches ``serve()``);
  * identical in-flight requests coalesce onto one lane and fan out with
    per-request billing (and ``dedup=False`` turns it off);
  * the warm compile cache is hit, not re-compiled, across pow2 resizes.
"""
import threading

import numpy as np
import pytest

from repro.core.scheduler import PartitionScheduler
from repro.fpp import FPPSession, MemoryModel
from repro.fpp.planner import auto_fused, autoscale_capacity, pow2_bucket
from repro.graphs.generators import grid2d, rmat
from repro.serve import GraphRequest, GraphServer


def _sources(g, k, seed=0):
    cand = np.flatnonzero(g.out_degree() > 0)
    return np.random.default_rng(seed).choice(cand, size=k, replace=False)


# ---------------------------------------------------------------- parity


@pytest.mark.parametrize("kind", ["sssp", "bfs", "ppr"])
def test_served_results_bit_identical_to_session_run(kind):
    g = grid2d(12, 12, seed=3)
    srcs = _sources(g, 4, seed=1)
    sess = FPPSession(g).plan(num_queries=len(srcs), block_size=32)
    one = sess.run(kind, srcs)
    # registering the session itself guarantees the served plan is the
    # same plan the one-shot run used
    server = GraphServer(capacity=len(srcs), k_visits=16)
    server.register_graph("g", sess)
    rids = [server.submit(GraphRequest(kind=kind, source=int(s), graph="g"))
            for s in srcs]
    server.serve()
    for i, rid in enumerate(rids):
        r = server.poll(rid)
        assert r is not None and r.status == "ok"
        np.testing.assert_array_equal(r.values, one.values[i], err_msg=kind)
        if kind == "ppr":
            np.testing.assert_array_equal(r.residual, one.residual[i])
        # per-request stats: exact integral edge work, billed host syncs
        assert r.stats["edges"] == round(r.stats["edges"])
        assert r.stats["edges"] == one.edges_processed[i]
        assert r.stats["host_syncs"] >= 1
        assert r.stats["visits"] >= 1


def test_mixed_two_tenant_two_graph_workload_end_to_end():
    """The ISSUE 5 acceptance workload: mixed sssp+ppr, two tenants, two
    graphs, interleaved submissions — every request answered, per-request
    stats attached, every answer bit-identical to the session run."""
    road = grid2d(10, 10, seed=6)
    social = rmat(7, 4, seed=7)
    road_s = _sources(road, 3, seed=2)
    soc_s = _sources(social, 3, seed=3)
    sess = {"road": FPPSession(road).plan(num_queries=3, block_size=32),
            "social": FPPSession(social).plan(num_queries=3, block_size=32)}
    want = {("road", "sssp"): sess["road"].run("sssp", road_s),
            ("social", "ppr"): sess["social"].run("ppr", soc_s)}

    server = GraphServer(capacity=3, k_visits=16)
    server.register_graph("road", sess["road"])
    server.register_graph("social", sess["social"])
    rids = []
    for i in range(3):      # interleave graphs, kinds, and tenants
        rids.append((("road", "sssp"), i, server.submit(GraphRequest(
            kind="sssp", source=int(road_s[i]), graph="road",
            tenant="alice" if i % 2 else "bob"))))
        rids.append((("social", "ppr"), i, server.submit(GraphRequest(
            kind="ppr", source=int(soc_s[i]), graph="social",
            tenant="bob" if i % 2 else "alice"))))
    out = server.serve()
    assert len(out) == len(rids)        # nothing dropped, nothing extra
    for key, i, rid in rids:
        r = out[rid]
        assert r.status == "ok"
        np.testing.assert_array_equal(r.values, want[key].values[i])
        for stat in ("visits", "edges", "host_syncs", "queue_wait_s",
                     "queue_wait_rounds", "latency_s"):
            assert stat in r.stats, (key, stat)


# --------------------------------------------------------------- fairness


def test_hot_tenant_cannot_starve_cold_tenant():
    """10x offered load from one tenant: the other tenant's queue wait
    stays bounded by the fair-share interleave, nowhere near the backlog
    a FIFO queue would impose."""
    g = grid2d(8, 8, seed=4)
    srcs = _sources(g, 10, seed=5)
    # dedup=False: the hot tenant reuses sources, and coalescing them
    # would dissolve the very backlog this test measures
    server = GraphServer(capacity=2, k_visits=16, autoscaler=None,
                         dedup=False)
    server.register_graph("g", g, num_queries=2, block_size=16)
    hot = [server.submit(GraphRequest(kind="sssp", source=int(srcs[i % 10]),
                                      graph="g", tenant="hot"))
           for i in range(20)]
    cold = [server.submit(GraphRequest(kind="sssp", source=int(s),
                                       graph="g", tenant="cold"))
            for s in srcs[:2]]
    out = server.serve()
    assert all(out[r].status == "ok" for r in hot + cold)
    cold_wait = max(out[r].stats["queue_wait_rounds"] for r in cold)
    hot_wait = max(out[r].stats["queue_wait_rounds"] for r in hot)
    # fair interleave admits a cold request within ~one fair-share cycle
    # of the 2-lane pool; the hot backlog (20 deep) waits far longer
    assert cold_wait <= 4, (cold_wait, hot_wait)
    assert hot_wait > cold_wait


def test_late_joining_tenant_neither_starved_nor_monopolist():
    """A tenant joining mid-serve, after the hot tenant has accrued
    virtual time, is caught up to the live pace: admissions after the
    join interleave instead of the newcomer draining its banked vtime as
    a monopoly burst (or, unfixed the other way, waiting out the whole
    hot backlog)."""
    g = grid2d(8, 8, seed=4)
    srcs = _sources(g, 10, seed=15)
    # result_cache off: cold reuses sources hot already finished, and a
    # cache hit skips admission entirely — this test is about admission
    server = GraphServer(capacity=1, k_visits=16, autoscaler=None,
                         result_cache=False)
    server.register_graph("g", g, num_queries=1, block_size=16)
    hot = [server.submit(GraphRequest(kind="sssp", source=int(srcs[i % 10]),
                                      graph="g", tenant="hot"))
           for i in range(8)]
    while len(server.responses) < 4:     # hot accrues vtime mid-serve
        assert server.step()
    join_round = server.rounds
    cold = [server.submit(GraphRequest(kind="sssp", source=int(s),
                                       graph="g", tenant="cold"))
            for s in srcs[:4]]
    out = server.serve()
    assert all(out[r].status == "ok" for r in hot + cold)

    def admit_round(r):
        # queue_wait_rounds is relative to the submit round: 0 for the
        # hot batch, join_round for the cold batch
        return ((0 if r in hot else join_round)
                + out[r].stats["queue_wait_rounds"])

    after = sorted((r for r in hot + cold if admit_round(r) >= join_round),
                   key=admit_round)
    tags = ["cold" if r in cold else "hot" for r in after]
    # post-join admissions interleave: neither the caught-up newcomer nor
    # the incumbent may run away with consecutive lanes
    for k in range(1, len(tags) + 1):
        c, h = tags[:k].count("cold"), tags[:k].count("hot")
        assert abs(c - h) <= 2, tags


def test_tenant_weights_shape_admission_order():
    """weight=2 buys two admissions per unit virtual time: in any prefix
    of the admission order the heavy tenant holds at most its share."""
    g = grid2d(8, 8, seed=4)
    srcs = _sources(g, 8, seed=6)
    server = GraphServer(capacity=1, k_visits=16, autoscaler=None)
    server.register_graph("g", g, num_queries=1, block_size=16)
    server.register_tenant("heavy", weight=2.0)
    server.register_tenant("light", weight=1.0)
    rids = {}
    for i in range(8):
        t = "heavy" if i < 4 else "light"
        rids[server.submit(GraphRequest(kind="sssp", source=int(srcs[i]),
                                        graph="g", tenant=t))] = t
    out = server.serve()
    order = sorted(rids, key=lambda r: out[r].stats["queue_wait_rounds"])
    admitted = [rids[r] for r in order]
    for k in range(1, len(admitted) + 1):
        heavy = admitted[:k].count("heavy")
        # 2:1 fair share, +1 slack for the start-time tie
        assert heavy <= (2 * k) // 3 + 1, admitted


# --------------------------------------------------------------- deadlines


def test_deadline_expired_rejected_not_silently_dropped():
    tick = [0.0]
    g = grid2d(8, 8, seed=4)
    server = GraphServer(capacity=2, k_visits=16, clock=lambda: tick[0],
                         autoscaler=None)
    server.register_graph("g", g, num_queries=2, block_size=16)
    srcs = _sources(g, 2, seed=7)
    keep = server.submit(GraphRequest(kind="sssp", source=int(srcs[0]),
                                      graph="g"))
    doomed = server.submit(GraphRequest(kind="sssp", source=int(srcs[1]),
                                        graph="g", deadline_s=5.0))
    tick[0] = 10.0                       # deadline lapses while queued
    out = server.serve()
    assert len(out) == 2                 # both answered — nothing dropped
    assert out[doomed].status == "expired"
    assert out[doomed].values is None
    assert out[doomed].stats["queue_wait_s"] == pytest.approx(10.0)
    assert out[keep].status == "ok" and out[keep].values is not None


def test_deadline_never_expires_admitted_requests():
    """Once a request holds a lane it runs to completion even if its
    deadline lapses mid-flight (rejection is an admission-time decision)."""
    tick = [0.0]
    g = grid2d(8, 8, seed=4)
    server = GraphServer(capacity=1, k_visits=4, clock=lambda: tick[0],
                         autoscaler=None)
    server.register_graph("g", g, num_queries=1, block_size=16)
    rid = server.submit(GraphRequest(kind="sssp",
                                     source=int(_sources(g, 1, seed=8)[0]),
                                     graph="g", deadline_s=5.0))
    assert server.step()                 # admitted at t=0
    tick[0] = 10.0                       # lapses while in flight
    out = server.serve()
    assert out[rid].status == "ok"


# --------------------------------------------------------------- isolation


def test_multi_graph_isolation_no_state_bleed():
    """Interleaved requests against two different-sized graphs: each
    answer has its own graph's shape and matches that graph's one-shot
    run exactly."""
    a, b = grid2d(9, 9, seed=9), grid2d(12, 12, seed=10)    # 81 vs 144
    sa, sb = _sources(a, 3, seed=11), _sources(b, 3, seed=12)
    sess = {"a": FPPSession(a).plan(num_queries=3, block_size=32),
            "b": FPPSession(b).plan(num_queries=3, block_size=32)}
    one = {"a": sess["a"].run("sssp", sa), "b": sess["b"].run("sssp", sb)}
    server = GraphServer(capacity=3, k_visits=8)
    server.register_graph("a", sess["a"])
    server.register_graph("b", sess["b"])
    rids = []
    for i in range(3):
        rids.append(("a", i, server.submit(GraphRequest(
            kind="sssp", source=int(sa[i]), graph="a"))))
        rids.append(("b", i, server.submit(GraphRequest(
            kind="sssp", source=int(sb[i]), graph="b"))))
    out = server.serve()
    for name, i, rid in rids:
        r = out[rid]
        assert r.values.shape == (sess[name].graph.n,)
        np.testing.assert_array_equal(r.values, one[name].values[i])


# ------------------------------------------------- priorities + arbitration


def test_request_priority_picks_pool_first():
    """A more urgent (lower-priority-value) request pulls its pool to the
    front of arbitration even when another pool queued first."""
    g = grid2d(8, 8, seed=4)
    srcs = _sources(g, 2, seed=13)
    server = GraphServer(capacity=1, k_visits=8, autoscaler=None)
    server.register_graph("g", g, num_queries=1, block_size=16)
    server.submit(GraphRequest(kind="sssp", source=int(srcs[0]), graph="g"))
    urgent = server.submit(GraphRequest(kind="bfs", source=int(srcs[1]),
                                        graph="g", priority=-1.0))
    server.step()                        # one round serves exactly one pool
    bfs_pool = server._pools[("g", "bfs")]
    sssp_pool = server._pools[("g", "sssp")]
    assert bfs_pool.exec.visits > 0      # urgent pool won arbitration
    assert sssp_pool.exec.visits == 0
    out = server.serve()
    assert out[urgent].status == "ok"


def test_scheduler_prefer_older_ties():
    """The serving tie-break: among priority ties pick the smallest stamp;
    the default contract (first index) is untouched."""
    sched = PartitionScheduler("priority", 3)
    prio = np.array([1.0, 1.0, 2.0], dtype=np.float32)
    stamp = np.array([7, 2, 0], dtype=np.int64)
    ops = np.array([1, 1, 1])
    assert sched.select(prio, stamp, ops) == 0                  # device rule
    assert sched.select(prio, stamp, ops, prefer_older_ties=True) == 1
    # all-empty still returns None either way
    inf = np.full(3, np.inf, dtype=np.float32)
    assert sched.select(inf, stamp, ops, prefer_older_ties=True) is None


# -------------------------------------------------------------- autoscale


def test_autoscale_capacity_hint_is_memory_clamped():
    mem = MemoryModel()
    kw = dict(mem=mem, n_vertices=1024, block_size=64)
    assert autoscale_capacity(0, 0, **kw) == 1           # idle shrinks
    assert autoscale_capacity(5, 1, **kw) == 8           # next pow2 >= 6
    assert autoscale_capacity(100, 0, max_capacity=16, **kw) == 16
    # a tiny VMEM budget caps the suggestion below raw demand
    tiny = MemoryModel(vmem_bytes=(2 * 64 * 64 + 2 * 8 * 64) * 4)
    got = autoscale_capacity(100, 0, mem=tiny, n_vertices=1024,
                             block_size=64)
    assert got <= 8 and tiny.fits(64, got, 1024)
    # the graph's staged blocks share HBM with the state planes
    blocks = mem.hbm_bytes - mem.state_bytes(1024, 2, 64)
    assert autoscale_capacity(100, 0, max_capacity=16,
                              adjacency_bytes=blocks, **kw) == 2


def test_server_grows_pool_capacity_under_backlog():
    g = grid2d(8, 8, seed=4)
    srcs = _sources(g, 6, seed=14)
    server = GraphServer(capacity=1, k_visits=16, max_capacity=8)
    server.register_graph("g", g, num_queries=1, block_size=16)
    rids = [server.submit(GraphRequest(kind="sssp", source=int(s),
                                       graph="g")) for s in srcs]
    out = server.serve()
    assert all(out[r].status == "ok" for r in rids)
    # the backlog of 6 should have pulled capacity up to the next pow2
    assert server._pools[("g", "sssp")].capacity == 8


# -------------------------------------------------- continuous batching


def test_concurrent_submitters_bit_identical_and_result_blocks():
    """Three client threads race submissions against the running lanes;
    every blocking ``result`` comes back bit-identical to the one-shot
    session run — a foreign-thread submit lands at a chunk boundary,
    indistinguishable from a quiet one."""
    g = grid2d(12, 12, seed=3)
    srcs = _sources(g, 12, seed=21)
    sess = FPPSession(g).plan(num_queries=4, block_size=32)
    one = sess.run("sssp", srcs)
    server = GraphServer(capacity=4, k_visits=16, autoscaler=None)
    server.register_graph("g", sess)
    server.start()
    try:
        rids, lock = {}, threading.Lock()

        def client(lo):
            for i in range(lo, lo + 4):
                rid = server.submit(GraphRequest(
                    kind="sssp", source=int(srcs[i]), graph="g",
                    tenant=f"t{lo}"))
                with lock:
                    rids[i] = rid
        threads = [threading.Thread(target=client, args=(lo,))
                   for lo in (0, 4, 8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, rid in rids.items():
            r = server.result(rid, timeout=120)
            assert r.status == "ok"
            np.testing.assert_array_equal(r.values, one.values[i])
        with pytest.raises(KeyError):
            server.result(10_000, timeout=1)
    finally:
        server.shutdown()


def test_failed_pump_is_raised_by_result_not_timed_out(monkeypatch):
    """A pump that raises on the device (a refused compile, an HBM OOM)
    must reach the caller: ``result()`` re-raises the very exception
    within the call instead of waiting out its timeout, and
    ``shutdown()`` raises it again after joining the lanes."""
    import time

    from repro.fpp.streaming import StreamingExecutor
    err = RuntimeError("RESOURCE_EXHAUSTED: device refused the chunk")

    def boom(self, max_visits):
        raise err

    monkeypatch.setattr(StreamingExecutor, "pump", boom)
    g = grid2d(6, 6, seed=1)
    server = GraphServer(capacity=1, k_visits=8, autoscaler=None)
    server.register_graph("g", g, num_queries=1, block_size=16)
    server.start()
    rid = server.submit(GraphRequest(kind="sssp", source=0, graph="g"))
    t0 = time.monotonic()
    with pytest.raises(RuntimeError) as got:
        server.result(rid, timeout=60)
    assert got.value is err
    assert time.monotonic() - t0 < 30
    with pytest.raises(RuntimeError) as again:
        server.shutdown()
    assert again.value is err


def test_concurrent_pump_failures_record_one_exception(monkeypatch):
    """Eight pools fail on eight threads at once (more lanes than cores,
    a shortened switch interval): exactly one exception is recorded, and
    every blocked ``result`` re-raises that same one."""
    import sys

    from repro.fpp.streaming import StreamingExecutor
    errors = []

    def boom(self, max_visits):
        err = RuntimeError(f"pump {id(self)}")
        errors.append(err)
        raise err

    monkeypatch.setattr(StreamingExecutor, "pump", boom)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        server = GraphServer(capacity=1, k_visits=8, autoscaler=None)
        for i in range(8):
            server.register_graph(f"g{i}", grid2d(4, 4, seed=i),
                                  num_queries=1, block_size=8)
        server.start()
        rids = [server.submit(GraphRequest(kind="sssp", source=0,
                                           graph=f"g{i}"))
                for i in range(8)]
        raised = set()
        for rid in rids:
            with pytest.raises(RuntimeError) as got:
                server.result(rid, timeout=60)
            raised.add(id(got.value))
        with pytest.raises(RuntimeError):
            server.shutdown()
    finally:
        sys.setswitchinterval(old)
    assert len(raised) == 1 and errors
    assert id(server._failure) in raised


def test_failed_prewarm_compile_is_raised_by_result(monkeypatch):
    """A background warm compile that fails is handed to the server, whose
    callers see it instead of a timeout."""
    import repro.serve.compile_cache as cc
    err = ValueError("compile refused")

    def refuse(*a, **k):
        raise err

    monkeypatch.setattr(cc, "build_warm_megastep", refuse)
    g = grid2d(6, 6, seed=1)
    server = GraphServer(capacity=1, k_visits=8, prewarm=("sssp",))
    server.register_graph("g", g, num_queries=1, block_size=16)
    for t in list(threading.enumerate()):
        if t.name.startswith("warm-"):
            t.join(timeout=60)
    assert server._failure is err
    server.start()
    rid = server.submit(GraphRequest(kind="sssp", source=0, graph="g"))
    with pytest.raises(ValueError) as got:
        server.result(rid, timeout=60)
    assert got.value is err
    with pytest.raises(ValueError):
        server.shutdown()


def test_serve_forever_matches_synchronous_serve():
    """The same mixed workload through the concurrent lanes and through
    the synchronous pump (the parity oracle): minplus answers are
    bit-identical; push answers agree within the eps the one-shot run
    carries (§3.3 — lane co-residency, and hence float accumulation
    order, legitimately differs across schedules); every request is
    answered with per-request stats."""
    g = grid2d(10, 10, seed=6)
    srcs = _sources(g, 6, seed=22)
    sess = FPPSession(g).plan(num_queries=2, block_size=32)
    reqs = [GraphRequest(kind=("sssp" if i % 2 else "ppr"),
                         source=int(srcs[i]), graph="g",
                         tenant="a" if i % 3 else "b")
            for i in range(6)]

    sync = GraphServer(capacity=2, k_visits=16, autoscaler=None)
    sync.register_graph("g", sess)
    sync_rids = sync.submit_all(reqs)
    sync_out = sync.serve()

    conc = GraphServer(capacity=2, k_visits=16, autoscaler=None)
    conc.register_graph("g", sess)
    conc_out = conc.serve_forever(iter([reqs]))
    assert not conc._running                 # lanes stopped after drain

    assert len(conc_out) == len(sync_out) == len(reqs)
    by_src_sync = {(sync_out[r].kind, sync_out[r].source): sync_out[r]
                   for r in sync_rids}
    for r in conc_out.values():
        assert r.status == "ok"
        want = by_src_sync[(r.kind, r.source)].values
        if r.kind == "ppr":
            np.testing.assert_allclose(r.values, want, atol=1e-3)
        else:
            np.testing.assert_array_equal(r.values, want)
        for stat in ("visits", "edges", "host_syncs", "latency_s"):
            assert stat in r.stats


def test_dedup_coalesces_in_flight_twins_and_bills_everyone():
    """Identical in-flight requests ride one lane: same bits out, the
    lane's work billed to every requester, ``fanout`` on the primary and
    ``coalesced`` on followers — and with one lane + three twins the pool
    only ever runs one query."""
    g = grid2d(10, 10, seed=6)
    src = int(_sources(g, 1, seed=23)[0])
    sess = FPPSession(g).plan(num_queries=1, block_size=32)
    one = sess.run("sssp", np.array([src]))
    server = GraphServer(capacity=1, k_visits=16, autoscaler=None)
    server.register_graph("g", sess)
    rids = [server.submit(GraphRequest(kind="sssp", source=src, graph="g",
                                       tenant=t))
            for t in ("a", "b", "c")]
    out = server.serve()
    assert len(out) == 3
    primary, followers = out[rids[0]], [out[r] for r in rids[1:]]
    assert primary.stats["fanout"] == 2
    assert all(f.stats["coalesced"] for f in followers)
    for r in [primary] + followers:
        assert r.status == "ok"
        np.testing.assert_array_equal(r.values, one.values[0])
        # per-request attribution: every requester billed the lane's work
        assert r.stats["visits"] == primary.stats["visits"] >= 1
        assert r.stats["edges"] == one.edges_processed[0]
    # one lane, one execution: the executor saw exactly one query
    assert server._pools[("g", "sssp")].exec._next_qid == 1


def test_dedup_off_serves_twins_separately():
    g = grid2d(8, 8, seed=4)
    src = int(_sources(g, 1, seed=24)[0])
    server = GraphServer(capacity=2, k_visits=16, autoscaler=None,
                         dedup=False)
    server.register_graph("g", g, num_queries=2, block_size=16)
    rids = [server.submit(GraphRequest(kind="sssp", source=src, graph="g"))
            for _ in range(2)]
    out = server.serve()
    assert all(out[r].status == "ok" for r in rids)
    assert not any(out[r].stats.get("coalesced") for r in rids)
    assert server._pools[("g", "sssp")].exec._next_qid == 2


def test_expired_dedup_primary_promotes_live_follower():
    """A coalescing primary whose deadline lapses while queued is
    rejected; its follower (no deadline) is promoted onto the backlog
    and still gets a real answer."""
    tick = [0.0]
    g = grid2d(8, 8, seed=4)
    srcs = _sources(g, 2, seed=25)
    server = GraphServer(capacity=1, k_visits=16, clock=lambda: tick[0],
                         autoscaler=None)
    server.register_graph("g", g, num_queries=1, block_size=16)
    # occupy the single lane so the twins stay queued
    blocker = server.submit(GraphRequest(kind="sssp", source=int(srcs[0]),
                                         graph="g"))
    doomed = server.submit(GraphRequest(kind="sssp", source=int(srcs[1]),
                                        graph="g", deadline_s=5.0))
    saved = server.submit(GraphRequest(kind="sssp", source=int(srcs[1]),
                                       graph="g", tenant="other"))
    tick[0] = 10.0                      # lapses while queued
    out = server.serve()
    assert out[doomed].status == "expired"
    assert out[saved].status == "ok" and out[saved].values is not None
    assert out[blocker].status == "ok"


def test_expired_primary_promotion_same_tenant_not_dropped():
    """Regression: policing rebuilds the heap it sweeps, and rejecting an
    expired coalescing primary promotes its follower into that same
    tenant's heap (the common case — the same tenant submitted the
    duplicate).  The promotion must land in the rebuilt heap, not be
    dropped by it, and each expired primary must be rejected exactly
    once even with several lapsing in one sweep."""
    tick = [0.0]
    g = grid2d(8, 8, seed=4)
    srcs = _sources(g, 3, seed=27)
    server = GraphServer(capacity=1, k_visits=16, clock=lambda: tick[0],
                         autoscaler=None)
    server.register_graph("g", g, num_queries=1, block_size=16)
    # occupy the single lane so everything below stays queued
    blocker = server.submit(GraphRequest(kind="sssp", source=int(srcs[0]),
                                         graph="g"))
    doomed, saved = [], []
    for s in srcs[1:]:
        doomed.append(server.submit(GraphRequest(
            kind="sssp", source=int(s), graph="g", deadline_s=5.0)))
        saved.append(server.submit(GraphRequest(
            kind="sssp", source=int(s), graph="g")))   # same tenant twin
    tick[0] = 10.0                      # both primaries lapse while queued
    out = server.serve()
    for rid in doomed:
        assert out[rid].status == "expired"
    for rid in saved:
        assert out[rid].status == "ok" and out[rid].values is not None
    assert out[blocker].status == "ok"
    assert server.pending == 0


def test_register_graph_invalid_prewarm_has_no_effect():
    """A register_graph rejected for a bad prewarm kind must leave no
    trace: the corrected retry succeeds instead of hitting 'already
    registered'."""
    g = grid2d(8, 8, seed=4)
    server = GraphServer(capacity=1, k_visits=16)
    with pytest.raises(ValueError, match="prewarm kind"):
        server.register_graph("g", g, prewarm=("sssp", "pagerank"),
                              num_queries=1, block_size=16)
    assert "g" not in server._sessions
    server.register_graph("g", g, prewarm=("sssp",),
                          num_queries=1, block_size=16)   # retry works


def test_warm_cache_shared_across_servers_and_resizes():
    """A pow2 capacity bucket's megastep compiles once into the shared
    cache; a second server over the same session resizes into a cache
    hit instead of recompiling — the bench's sweep-point pattern."""
    g = grid2d(8, 8, seed=4)
    srcs = _sources(g, 6, seed=26)
    server = GraphServer(capacity=1, k_visits=16, max_capacity=8)
    server.register_graph("g", g, num_queries=1, block_size=16)
    rids = [server.submit(GraphRequest(kind="sssp", source=int(s),
                                       graph="g")) for s in srcs]
    out = server.serve()
    assert all(out[r].status == "ok" for r in rids)
    assert server._pools[("g", "sssp")].capacity == 8   # grew via resize
    compiled = server.cache.stats()["misses"]

    twin = GraphServer(capacity=1, k_visits=16, max_capacity=8,
                       cache=server.cache)
    twin.register_graph("g", server._sessions["g"])     # same session
    rids = [twin.submit(GraphRequest(kind="sssp", source=int(s),
                                     graph="g")) for s in srcs]
    out = twin.serve()
    assert all(out[r].status == "ok" for r in rids)
    stats = twin.cache.stats()
    assert stats["misses"] == compiled, stats   # no new compiles
    assert stats["hits"] >= 1, stats            # twin's resize hit warmth
    # every compiled capacity is a pow2 bucket
    assert all(k[3] == pow2_bucket(k[3]) for k in server.cache._cache)


def test_warm_cache_keys_by_session_not_graph_name():
    """Two servers sharing one cache, each calling a *different* graph by
    the same name: the second must never be handed the first's executable
    (same structure, different weights — a collision would be silently
    wrong values, not a shape error)."""
    from repro.serve import MegastepCache
    g1 = grid2d(8, 8, seed=1)
    g2 = grid2d(8, 8, seed=2)           # same shape, different weights
    src = int(_sources(g1, 1, seed=28)[0])
    cache = MegastepCache()
    s1 = GraphServer(capacity=2, k_visits=16, autoscaler=None, cache=cache)
    s1.register_graph("default", g1, num_queries=2, block_size=16)
    s1._warm_executable(s1._pool("default", "sssp"), 2)   # warm g1's key
    s2 = GraphServer(capacity=2, k_visits=16, autoscaler=None, cache=cache)
    s2.register_graph("default", g2, num_queries=2, block_size=16)
    rid = s2.submit(GraphRequest(kind="sssp", source=src, graph="default"))
    out = s2.serve()
    expected = FPPSession(g2).plan(num_queries=2, block_size=16).run(
        "sssp", [src])
    np.testing.assert_array_equal(out[rid].values, expected.values[0])
    # warming g2's pool lands a second entry, not a name-collision hit
    s2._warm_executable(s2._pool("default", "sssp"), 2)
    assert cache.stats()["size"] == 2


# ------------------------------------------------------- planner dispatch


def test_pow2_bucket_snaps_and_clamps():
    assert pow2_bucket(0) == 1
    assert pow2_bucket(1) == 1
    assert pow2_bucket(5) == 8
    assert pow2_bucket(8) == 8
    assert pow2_bucket(9) == 16
    assert pow2_bucket(10_000, max_capacity=64) == 64
    assert pow2_bucket(2, min_capacity=4) == 4


def test_auto_fused_follows_committed_yardsticks():
    # minplus kinds: fused won both committed K points
    assert auto_fused("sssp", 64) and auto_fused("sssp", 8)
    assert auto_fused("bfs", 64)        # bfs shares sssp's minplus body
    # ppr: the XLA megastep beat fused at both committed K points
    assert not auto_fused("ppr", 64) and not auto_fused("ppr", 8)
    # off-grid K resolves via the nearest committed yardstick
    assert auto_fused("sssp", 16) in (True, False)


def test_auto_fused_guards_dense_block_graphs():
    """Past the planner's dmax budget the fused kernel's pre-gathered
    adjacency grows linearly in dmax; the auto-select must fall back to
    the XLA megastep (an explicit fused=True is never overridden)."""
    from repro.fpp.planner import FUSED_DMAX_BUDGET
    assert auto_fused("sssp", 64, dmax=FUSED_DMAX_BUDGET)
    assert not auto_fused("sssp", 64, dmax=FUSED_DMAX_BUDGET + 1)
    # a dense-partitioned graph resolves to the XLA megastep end to end:
    # an ER graph's block adjacency is near-complete, dmax ~ P-1
    from repro.graphs.generators import erdos_renyi
    g = erdos_renyi(n=1024, avg_deg=4.0, seed=3)
    sess = FPPSession(g).plan(num_queries=2, block_size=32, fused="auto")
    bg, _ = sess.prepared()
    assert bg.nbr_part.shape[1] > FUSED_DMAX_BUDGET
    assert sess.current_plan.resolve_fused(
        "sssp", dmax=bg.nbr_part.shape[1]) is False
    server = GraphServer(capacity=2, k_visits=8)
    server.register_graph("er", sess)
    assert server._warm_params(sess, "sssp")["fused"] is False


def test_plan_fused_auto_resolves_per_kind():
    g = grid2d(8, 8, seed=4)
    sess = FPPSession(g).plan(num_queries=2, block_size=16, fused="auto")
    p = sess.current_plan
    assert p.fused == "auto"
    assert p.resolve_fused("sssp") is True
    assert p.resolve_fused("ppr") is False
    with pytest.raises(ValueError):
        FPPSession(g).plan(num_queries=2, fused="sometimes")


# ------------------------------------------------------------------ misc


def test_submit_validation_and_empty_serve():
    g = grid2d(6, 6, seed=15)
    server = GraphServer(capacity=2)
    server.register_graph("g", g, num_queries=2, block_size=16)
    with pytest.raises(ValueError):
        server.submit(GraphRequest(kind="dfs", source=0, graph="g"))
    with pytest.raises(ValueError):
        server.submit(GraphRequest(kind="sssp", source=0, graph="nope"))
    with pytest.raises(ValueError):
        server.submit(GraphRequest(kind="sssp", source=g.n, graph="g"))
    with pytest.raises(ValueError):
        server.register_graph("g", g)    # duplicate name
    assert server.serve() == {}          # nothing submitted: clean no-op
    assert server.pending == 0
