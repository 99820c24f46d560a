"""Device-resident scheduling (ISSUE 4): the K-visit megastep.

What these tests pin:
  * megastep results match the legacy per-visit host loop for all four
    scheduler policies x both visit-algebra modes — bit-identical for
    minplus (and for push under the deterministic policies, where the
    visit sequences coincide), within the ACL eps tolerance for push under
    ``random`` (different seeded streams, same guarantee);
  * the host ``PartitionScheduler`` is the oracle: ``device_select``
    reproduces its deterministic argmin/argmax choices bit-for-bit,
    first-index ties included;
  * the on-device ``random`` policy is seeded and replayable (same seed =>
    same visit order and same values);
  * ``FPPEngine.run`` performs O(visits/K) host synchronizations;
  * a staggered streaming run through chunked megastep pumps still equals
    the one-shot run of the union (admission/harvest at chunk boundaries);
  * an engine with the constants of an earlier one over the same graph
    runs the program that one traced, and every constant the trace closes
    over keys a program of its own.
"""
import sys
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import oracles  # noqa: E402
from repro.core import visit as V  # noqa: E402
from repro.core.engine import FPPEngine  # noqa: E402
from repro.core.partition import partition  # noqa: E402
from repro.core.scheduler import POLICIES, PartitionScheduler  # noqa: E402
from repro.core.yielding import YieldConfig  # noqa: E402
from repro.fpp import FPPSession  # noqa: E402
from repro.graphs.generators import grid2d, rmat  # noqa: E402


def _minplus_setup():
    g = grid2d(12, 12, seed=0)
    bg, perm = partition(g, 32, method="bfs")
    return g, bg, perm, perm[np.array([0, 70, 143])]


def _push_setup():
    g = rmat(8, 6, seed=5)
    bg, perm = partition(g, 64, method="bfs")
    deg = g.out_degree()
    srcs_o = np.random.default_rng(0).choice(np.flatnonzero(deg > 0), 3,
                                             replace=False)
    return g, bg, perm, srcs_o, perm[srcs_o]


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("K", [1, 8, 64])
def test_megastep_minplus_bit_identical_to_host_loop(policy, K):
    """minplus is order-independent down to the bit (every candidate is the
    same left-associated path sum), so even the random policy — which visits
    in a different seeded order on device — must agree exactly."""
    _, bg, _, srcs = _minplus_setup()
    eng = FPPEngine(bg, mode="minplus", num_queries=len(srcs),
                    schedule=policy, k_visits=K)
    host = eng.run(srcs, host_loop=True, record_order=True)
    mega = eng.run(srcs, record_order=True)
    np.testing.assert_array_equal(
        np.nan_to_num(mega.values, posinf=1e30),
        np.nan_to_num(host.values, posinf=1e30))
    if policy != "random":
        # deterministic policies replay the exact host visit sequence
        assert mega.visit_order == host.visit_order
        np.testing.assert_array_equal(mega.edges_processed,
                                      host.edges_processed)
        assert mega.stats.visits == host.stats.visits


@pytest.mark.parametrize("policy", POLICIES)
def test_megastep_push_matches_host_loop_and_oracle(policy):
    g, bg, perm, srcs_o, srcs = _push_setup()
    eps = 1e-4
    deg = np.maximum(g.out_degree(), 1)
    eng = FPPEngine(bg, mode="push", num_queries=len(srcs),
                    schedule=policy, eps=eps, k_visits=64)
    host = eng.run(srcs, host_loop=True)
    mega = eng.run(srcs)
    if policy != "random":
        # same visit sequence => same float arithmetic, bit for bit
        np.testing.assert_array_equal(mega.values, host.values)
        np.testing.assert_array_equal(mega.residual, host.residual)
    for qi, s in enumerate(srcs_o):
        want_p, _, _ = oracles.ppr_push(g, int(s), eps=eps)
        err = np.abs(mega.values[qi][perm] - want_p) / deg
        assert err.max() <= 2 * eps, (policy, qi)
        mass = mega.values[qi].sum() + mega.residual[qi].sum()
        assert abs(mass - 1.0) < 5e-3, (policy, qi)


@pytest.mark.parametrize("mode", ["minplus", "push"])
def test_megastep_sync_count_is_o_visits_over_k(mode):
    """The acceptance bound: one host consultation per K-visit chunk (+1
    final empty chunk for termination), against visits for the host loop."""
    if mode == "minplus":
        _, bg, _, srcs = _minplus_setup()
        kw = {}
    else:
        _, bg, _, _, srcs = _push_setup()
        kw = {"eps": 1e-3}
    for K in (1, 8, 64):
        eng = FPPEngine(bg, mode=mode, num_queries=len(srcs), k_visits=K,
                        **kw)
        res = eng.run(srcs)
        assert res.stats.visits > 0
        assert res.stats.host_syncs <= -(-res.stats.visits // K) + 1, \
            (mode, K, res.stats.host_syncs, res.stats.visits)
        host = eng.run(srcs, host_loop=True)
        assert host.stats.host_syncs == host.stats.visits


@pytest.mark.parametrize("kind", ["sssp", "ppr"])
def test_lane_visits_equal_the_host_loops_count(kind):
    """``lane_visits`` -- the query lanes that processed an edge, summed
    over the visits -- rides the megastep's tallies beside the rounds and
    equals the count the host loop takes from each visit's ``eq``."""
    if kind == "sssp":
        _, bg, _, srcs = _minplus_setup()
        eng = FPPEngine(bg, mode="minplus", num_queries=len(srcs),
                        k_visits=8)
    else:
        _, bg, _, _, srcs = _push_setup()
        eng = FPPEngine(bg, mode="push", num_queries=len(srcs), eps=1e-4,
                        k_visits=8)
    host = eng.run(srcs, host_loop=True)
    mega = eng.run(srcs)
    assert mega.stats.visits == host.stats.visits
    assert mega.stats.rounds == host.stats.rounds
    assert mega.stats.lane_visits == host.stats.lane_visits
    assert 0 < host.stats.lane_visits <= host.stats.visits * len(srcs)
    # one harvest read carries both tallies: the chunk's reads stay five
    assert mega.stats.chunk_reads == 5 * mega.stats.host_syncs


@pytest.mark.parametrize("kind", ["sssp", "ppr"])
def test_streaming_counts_the_lane_visits_of_the_one_shot_run(kind):
    """A stream that holds the whole batch from the start visits as the
    one-shot run does, so its chunked and stepped paths both count the
    run's lane visits."""
    g = grid2d(12, 12, seed=6)
    srcs = np.array([0, 40, 80, 120, 143, 7])
    sess = FPPSession(g).plan(num_queries=len(srcs), block_size=32)
    one = sess.run(kind, srcs)
    chunked = sess.stream(kind, capacity=len(srcs), k_visits=16)
    chunked.submit(srcs)
    chunked.run()
    stepped = sess.stream(kind, capacity=len(srcs))
    stepped.submit(srcs)
    while stepped.step():
        pass
    assert chunked.visits == stepped.visits == one.stats["visits"]
    assert (chunked.lane_visits == stepped.lane_visits
            == one.stats["lane_visits"] > 0)


def test_megastep_respects_max_visits_exactly():
    """The dynamic ``limit`` operand caps a chunk mid-K, so max_visits keeps
    per-visit granularity without recompiling."""
    _, bg, _, srcs = _minplus_setup()
    eng = FPPEngine(bg, mode="minplus", num_queries=len(srcs), k_visits=64)
    for cap in (1, 5, 7):
        res = eng.run(srcs, max_visits=cap, record_order=True)
        assert res.stats.visits == cap
        assert len(res.visit_order) == cap


def test_device_select_matches_host_scheduler_oracle():
    """Deterministic device policies reproduce the host argmin/argmax
    bit-for-bit (including first-index tie-breaks); random stays inside the
    non-empty set."""
    rng = np.random.default_rng(3)
    key = jax.random.PRNGKey(0)
    for trial in range(20):
        P = int(rng.integers(2, 17))
        prio = np.where(rng.random(P) < 0.4, np.inf,
                        rng.integers(0, 4, P)).astype(np.float32)  # many ties
        if not np.isfinite(prio).any():
            prio[int(rng.integers(P))] = 1.0
        stamp = np.where(np.isfinite(prio),
                         rng.integers(0, 3, P),
                         np.iinfo(np.int32).max - 1).astype(np.int32)
        ops = np.where(np.isfinite(prio), rng.integers(1, 4, P),
                       0).astype(np.int32)
        for policy in ("priority", "fifo", "max_ops"):
            sched = PartitionScheduler(policy, P)
            want = sched.select(prio, stamp, ops)
            got = int(V.device_select(policy, jnp.asarray(prio),
                                      jnp.asarray(stamp), jnp.asarray(ops),
                                      key))
            assert got == want, (trial, policy)
        key, sub = jax.random.split(key)
        r = int(V.device_select("random", jnp.asarray(prio),
                                jnp.asarray(stamp), jnp.asarray(ops), sub))
        assert np.isfinite(prio[r]), trial


def test_random_policy_seeded_determinism():
    """Same seed => same on-device threefry stream => identical visit order
    and bit-identical results, run-to-run and engine-to-engine."""
    _, bg, _, srcs = _minplus_setup()

    def once(seed):
        eng = FPPEngine(bg, mode="minplus", num_queries=len(srcs),
                        schedule="random", seed=seed, k_visits=8)
        res = eng.run(srcs, record_order=True)
        return res.values, res.visit_order

    v1, o1 = once(7)
    v2, o2 = once(7)
    assert o1 == o2
    np.testing.assert_array_equal(v1, v2)
    # a replayed run on the SAME engine restarts the stream too
    eng = FPPEngine(bg, mode="minplus", num_queries=len(srcs),
                    schedule="random", seed=7, k_visits=8)
    ra = eng.run(srcs, record_order=True)
    rb = eng.run(srcs, record_order=True)
    assert ra.visit_order == rb.visit_order == o1


@pytest.mark.parametrize("kind,K", [("sssp", 1), ("sssp", 8), ("ppr", 8)])
def test_streaming_staggered_chunked_matches_one_shot(kind, K):
    """Admission and harvest at K-visit chunk boundaries preserve the
    streaming exactness contract (DESIGN.md §3.3): a staggered run equals
    the one-shot union — bitwise for minplus, within eps for push."""
    g = grid2d(12, 12, seed=6)
    srcs = np.array([0, 40, 80, 120, 143, 7])
    eps = 1e-3
    sess = FPPSession(g).plan(num_queries=len(srcs), block_size=32)
    one = sess.run(kind, srcs, eps=eps)
    stream = sess.stream(kind, capacity=4, eps=eps, k_visits=K)
    qids = stream.submit(srcs[:3])
    stream.pump(3)                       # in-flight work between arrivals
    qids += stream.submit(srcs[3:])
    out = stream.run()
    assert len(out) == len(srcs)
    # chunked dispatch: at most one sync per chunk plus the empty
    # terminal/boundary chunks (one per pump round)
    assert stream.host_syncs <= -(-stream.visits // K) + 4
    if K > 1:
        assert stream.host_syncs < stream.visits
    deg = np.maximum(g.out_degree(), 1)
    for i, qid in enumerate(qids):
        if kind == "sssp":
            np.testing.assert_array_equal(out[qid], one.values[i])
        else:
            diff = np.abs(out[qid] - one.values[i]) / deg
            assert diff.max() <= 4 * eps, (i, diff.max())


def test_streaming_step_path_matches_chunked_pump():
    """The legacy per-visit ``step()`` path (host scheduler + harvest_every
    cadence) stays pinned against the chunked megastep pump — the two
    streaming drivers must not drift apart."""
    g = grid2d(10, 10, seed=2)
    srcs = np.array([0, 25, 50, 75, 99])
    sess = FPPSession(g).plan(num_queries=len(srcs), block_size=16)
    chunked = sess.stream("sssp", capacity=3)
    chunked.submit(srcs)
    out_pump = chunked.run()
    stepped = sess.stream("sssp", capacity=3, harvest_every=2)
    stepped.submit(srcs)
    while stepped.step():
        pass
    stepped._harvest()
    out_step = {qid: q.values for qid, q in stepped.queries.items()
                if q.done}
    assert set(out_pump) == set(out_step) == set(range(len(srcs)))
    for qid in out_pump:
        np.testing.assert_array_equal(out_pump[qid], out_step[qid])
    # the whole point of the chunked path: far fewer host consultations
    assert chunked.host_syncs < stepped.visits


@pytest.mark.parametrize("mode", ["minplus", "push"])
@pytest.mark.parametrize("lanes", [0, 3, 8])
def test_device_made_state_is_the_host_made_state_bit_for_bit(mode, lanes):
    """``init_engine_state`` fills the planes and the buffer on the device;
    they equal ``init_dense_state``'s host arrays bit for bit, sources on
    some lanes (streaming admission) or all of them (a run)."""
    g = grid2d(12, 12, seed=2)
    bg, perm = partition(g, 32)
    eng = FPPEngine(bg, mode=mode, num_queries=8)
    sources = perm[np.random.default_rng(lanes).choice(g.n, lanes,
                                                       replace=False)]
    state = V.init_engine_state(eng.algebra, eng.dg, sources, num_queries=8)
    planes, buf = V.init_dense_state(eng.algebra, bg.num_parts, 8,
                                     bg.block_size, sources)
    for got, want in zip((*state.planes, state.buf), (*planes, buf)):
        got = np.asarray(got)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()


# ---------------------------------------------------------------------------
# one traced program per graph and constants


def _same_bits(a, b):
    assert a.stats.visits == b.stats.visits
    np.testing.assert_array_equal(a.edges_processed, b.edges_processed)
    for x, y in ((a.values, b.values), (a.residual, b.residual)):
        assert (x is None) == (y is None)
        if x is not None:
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


@pytest.mark.parametrize("kind", ["sssp", "ppr"])
def test_second_run_of_a_plan_reuses_its_traced_megastep(kind):
    g = grid2d(12, 12, seed=3)
    srcs = np.array([0, 50, 100, 143])
    sess = FPPSession(g).plan(num_queries=4, block_size=32)
    first = sess.run(kind, srcs)
    again = sess.run(kind, srcs)
    fresh = FPPSession(g).plan(num_queries=4, block_size=32).run(kind, srcs)
    assert first.stats["megastep_traces"] == 1
    assert again.stats["megastep_traces"] == 0
    assert fresh.stats["megastep_traces"] == 1
    for res in (again, fresh):
        np.testing.assert_array_equal(res.edges_processed,
                                      first.edges_processed)
        assert res.values.tobytes() == first.values.tobytes()


_BASE = {"minplus": dict(mode="minplus", yield_config=YieldConfig(delta=2.0),
                         k_visits=8),
         "push": dict(mode="push", alpha=0.15, eps=1e-3, k_visits=8)}

#: (mode, the engine arguments that change one constant of the program)
_ONE_CONSTANT = {
    "delta": ("minplus", dict(yield_config=YieldConfig(delta=3.0))),
    "max_rounds": ("minplus",
                   dict(yield_config=YieldConfig(delta=2.0, max_rounds=3))),
    "k_visits": ("minplus", dict(k_visits=16)),
    "schedule": ("minplus", dict(schedule="fifo")),
    "fused": ("minplus", dict(fused=True)),
    "harvest_mask": ("minplus", {}),
    "alpha": ("push", dict(alpha=0.2)),
    "eps": ("push", dict(eps=2e-3)),
}


@pytest.mark.parametrize("constant", list(_ONE_CONSTANT))
def test_each_program_constant_traces_a_program_of_its_own(constant):
    """An engine that differs from a warm one in one constant the trace
    closes over traces exactly once, and computes the bits of the same
    engine built over a fresh graph (a table of its own)."""
    mode, change = _ONE_CONSTANT[constant]
    if mode == "minplus":
        g, bg, _, srcs = _minplus_setup()
    else:
        g, bg, _, _, srcs = _push_setup()
    fresh_bg, _ = partition(g, bg.block_size, method="bfs")
    warm = FPPEngine(bg, num_queries=len(srcs), **_BASE[mode])
    assert warm.run(srcs).stats.megastep_traces == 1
    kw = {**_BASE[mode], **change}
    eng = FPPEngine(bg, num_queries=len(srcs), **kw)
    fresh = FPPEngine(fresh_bg, num_queries=len(srcs), **kw)
    if constant == "harvest_mask":
        for e in (eng, fresh):
            e._megastep = V.make_megastep(
                e.dg, e.algebra, e.max_rounds, K=e.k_visits,
                harvest_mask=True, programs=e.programs)
    assert not eng._megastep.reused
    got = eng.run(srcs)
    assert got.stats.megastep_traces == 1
    _same_bits(got, fresh.run(srcs))
    assert eng.run(srcs).stats.megastep_traces == 0


def test_a_wrapped_make_megastep_sees_every_chunk_of_a_reused_program():
    """The benchmark records each chunk's visit counts by wrapping
    ``visit.make_megastep``; a run that reuses the warm program still
    builds its bound through it."""
    from chipbench.traffic.batch import visit_counts
    g = grid2d(24, 24, seed=1)
    srcs = np.array([0, 100, 300, 575])
    sess = FPPSession(g).plan(num_queries=4, block_size=16)
    sess.run("sssp", srcs)
    sink = []
    with visit_counts(sink):
        res = sess.run("sssp", srcs)
    assert res.stats["megastep_traces"] == 0
    assert len(sink) == res.stats["host_syncs"] > 1
    np.testing.assert_array_equal(
        np.sum([np.asarray(c) for c in sink], axis=0),
        res.stats["visit_counts"])


def test_threads_building_one_megastep_share_one_program():
    """The serving pools warm their programs on threads of their own:
    bounds built at once over one table build one program and share it."""
    _, bg, _, srcs = _minplus_setup()
    eng = FPPEngine(bg, num_queries=len(srcs), k_visits=4)
    bounds, start = [], threading.Barrier(16)

    def build():
        start.wait(timeout=60)
        bounds.append(V.make_megastep(eng.dg, eng.algebra, eng.max_rounds,
                                      K=4, harvest_mask=True,
                                      programs=eng.programs))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=build) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and len(bounds) == 16
    assert len({id(b.program) for b in bounds}) == 1
    assert sum(not b.reused for b in bounds) == 1
    assert bounds[0].program is not eng._megastep.program
