"""Application-level tests: BC, LL, NCP, RW, baselines, schedulers."""
import numpy as np
import pytest

from repro.core import applications as apps, oracles
from repro.core.baselines import global_minplus, global_push
from repro.core.partition import edge_cut_fraction, partition
from repro.core.queries import prepare, run_rw, run_sssp
from repro.core.scheduler import PartitionScheduler
from repro.core.support import SupportRows
from repro.core.yielding import YieldConfig
from repro.graphs.generators import build_suite, grid2d, rmat


def _brandes_oracle(g, sources):
    bc = np.zeros(g.n)
    for s in sources:
        dist, sigma, _ = oracles.bfs_sigma(g, int(s))
        sig, delta = apps._sigma_delta(g, dist)
        np.testing.assert_allclose(sig, sigma)
        delta[s] = 0.0
        bc += delta
    return bc


def test_bc_matches_brandes():
    g = rmat(7, 4, seed=0, weighted=False)
    srcs = np.array([0, 17, 90, 111])
    want = _brandes_oracle(g, srcs)
    got, _ = apps.betweenness_centrality(g, srcs, block_size=32)
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_bc_star_graph_analytic():
    """Star: all shortest paths between leaves pass the hub."""
    from repro.core.graph import CSRGraph
    n = 9
    hub = 0
    src = [hub] * (n - 1)
    dst = list(range(1, n))
    g = CSRGraph.from_edges(n, src, dst, symmetrize=True)
    srcs = np.arange(n)
    got, _ = apps.betweenness_centrality(g, srcs, block_size=4)
    # each of the (n-1)(n-2) ordered leaf pairs contributes 1 to the hub
    assert got[hub] == pytest.approx((n - 1) * (n - 2))
    assert np.allclose(got[1:], 0.0)


def test_landmark_labels_triangle_inequality():
    g = grid2d(10, 10, seed=1)
    lms = np.array([0, 55, 99])
    labels, _ = apps.landmark_labeling(g, lms, block_size=32)
    for i, s in enumerate(lms):
        d_or, _ = oracles.dijkstra(g, int(s))
        np.testing.assert_allclose(
            np.nan_to_num(labels.dists[i], posinf=1e30),
            np.nan_to_num(d_or, posinf=1e30), atol=1e-3)
    # landmark estimate is an upper bound on true distance
    rng = np.random.default_rng(0)
    us = rng.choice(g.n, 10)
    vs = rng.choice(g.n, 10)
    est = labels.query(us, vs)
    for u, v, e in zip(us, vs, est):
        d_or, _ = oracles.dijkstra(g, int(u))
        assert e >= d_or[v] - 1e-3


def test_ncp_conductance_valid():
    g = rmat(8, 6, seed=2)
    seeds = np.array([1, 50, 200])
    best, _ = apps.ncp(g, seeds, block_size=64, eps=1e-4)
    finite = best[np.isfinite(best)]
    assert finite.size > 0
    assert (finite >= 0).all() and (finite <= 1.0 + 1e-6).all()


def test_sweep_conductance_whole_graph_is_zero_cut():
    g = grid2d(6, 6, seed=3)
    p = np.ones(g.n)  # whole graph in support
    sizes, cond = apps.sweep_conductance(g, p)
    # the full set has cut 0 but denominator 0 -> inf; the half set is finite
    assert sizes[-1] == g.n
    assert np.isfinite(cond[: g.n // 2]).any()


def test_random_walks_complete_and_deterministic():
    g = rmat(7, 6, seed=4, weighted=False)
    bg, perm = prepare(g, 32, unit_weights=True)
    deg = g.out_degree()
    srcs = perm[np.random.default_rng(1).choice(
        np.flatnonzero(deg > 0), 8, replace=False)]
    r1 = run_rw(bg, srcs, length=12, seed=7)
    r2 = run_rw(bg, srcs, length=12, seed=7)
    assert (r1.steps == 12).all()
    assert (r1.trajectory_hash == r2.trajectory_hash).all()  # deterministic
    # positions are real vertices
    assert (r1.positions < bg.n_padded).all()


def test_baseline_global_minplus_exact():
    g = build_suite("road-ca", seed=0)
    # subsample for speed: use smaller instance
    g = grid2d(14, 14, seed=0)
    bg, perm = partition(g, 32)
    srcs = np.array([0, 50, 170])
    bl = global_minplus(bg, perm[srcs])
    for qi, s in enumerate(srcs):
        d_or, _ = oracles.dijkstra(g, int(s))
        np.testing.assert_allclose(
            np.nan_to_num(bl.values[qi][perm], posinf=1e30),
            np.nan_to_num(d_or, posinf=1e30), atol=1e-3)


def test_baseline_global_push_invariants():
    g = rmat(7, 6, seed=5)
    bg, perm = partition(g, 32)
    deg = g.out_degree()
    srcs = np.random.default_rng(2).choice(np.flatnonzero(deg > 0), 3,
                                           replace=False)
    bl = global_push(bg, perm[srcs], eps=1e-4)
    assert (bl.edges_processed > 0).all()
    assert bl.modeled_bytes >= bl.modeled_bytes_shared


def test_forkgraph_traffic_below_uncoordinated_baseline():
    """The paper's headline: buffered execution cuts memory traffic (Fig 10)."""
    g = grid2d(24, 24, seed=6)
    bg, perm = partition(g, 32)
    srcs = perm[np.random.default_rng(3).choice(g.n, 8, replace=False)]
    res = run_sssp(bg, srcs, yield_config=YieldConfig(delta=4.0))
    bl = global_minplus(bg, srcs)
    assert res.stats.modeled_bytes < bl.modeled_bytes


def test_scheduler_policies_select_validly():
    s = PartitionScheduler("priority", 4)
    prio = np.array([np.inf, 3.0, 1.0, np.inf], np.float32)
    stamp = np.array([9, 5, 7, 9], np.int32)
    ops = np.array([0, 2, 1, 0], np.int32)
    assert s.select(prio, stamp, ops) == 2
    assert PartitionScheduler("fifo", 4).select(prio, stamp, ops) == 1
    assert PartitionScheduler("max_ops", 4).select(prio, stamp, ops) == 1
    assert PartitionScheduler("random", 4).select(prio, stamp, ops) in (1, 2)
    done = np.full(4, np.inf, np.float32)
    assert s.select(done, stamp, ops) is None


def test_priority_schedule_no_worse_work_than_random_on_road():
    """Table 4A's direction: priority <= random on road-like graphs."""
    g = grid2d(20, 20, seed=7)
    bg, perm = partition(g, 32)
    srcs = perm[np.array([0, 399, 210, 25])]
    yc = YieldConfig(delta=2.0)
    w_pri = run_sssp(bg, srcs, yield_config=yc,
                     schedule="priority").edges_processed.sum()
    w_rnd = run_sssp(bg, srcs, yield_config=yc,
                     schedule="random").edges_processed.sum()
    assert w_pri <= w_rnd * 1.2  # allow noise; typically much lower


def test_partition_bfs_beats_random_cut_on_grid():
    g = grid2d(20, 20, seed=8)
    bg_bfs, _ = partition(g, 32, method="bfs")
    bg_rnd, _ = partition(g, 32, method="random")
    assert edge_cut_fraction(bg_bfs) < edge_cut_fraction(bg_rnd)


def _ppr_like(n, rng, k, tied):
    """A PPR-shaped vector on ``k`` random vertices; ``tied`` draws from a
    few scores so that -p/deg ties and the id order breaks them."""
    p = np.zeros(n, np.float32)
    at = rng.choice(n, k, replace=False)
    p[at] = rng.choice([0.5, 0.25, 0.125], k) if tied else rng.random(k)
    return p


GRAPHS = {"rmat": lambda: rmat(8, 6, seed=2),
          "lattice": lambda: grid2d(13, 11, seed=3)}


@pytest.mark.parametrize("support", ["empty", "single", "pair", "small",
                                     "half", "whole"])
@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_sweep_conductance_is_the_whole_graph_reference_bit_for_bit(
        graph, tied, support):
    g = GRAPHS[graph]()
    k = {"empty": 0, "single": 1, "pair": 2, "small": 17, "half": g.n // 2,
         "whole": g.n}[support]
    rng = np.random.default_rng([k, int(tied)])
    for _ in range(3):
        p = _ppr_like(g.n, rng, k, tied)
        sizes, cond = apps.sweep_conductance(g, p)
        want_sizes, want_cond = oracles.sweep_conductance_ref(g, p)
        assert sizes.dtype == want_sizes.dtype
        np.testing.assert_array_equal(sizes, want_sizes)
        np.testing.assert_array_equal(cond, want_cond)
        assert sizes.size == (k if k >= 2 else 0)


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_ncp_profile_is_the_binwise_minimum_of_the_reference_sweeps(graph):
    g = GRAPHS[graph]()
    rng = np.random.default_rng(7)
    pvals = np.stack([_ppr_like(g.n, rng, k, tied)
                      for k in (0, 1, 2, 5, 40, g.n // 3, 0, g.n)
                      for tied in (False, True)])
    nbins = int(np.ceil(np.log2(g.n))) + 1
    want = np.full(nbins, np.inf)
    for p in pvals:
        sizes, cond = oracles.sweep_conductance_ref(g, p)
        if sizes.size:
            np.minimum.at(want, np.minimum(np.log2(sizes).astype(np.int64),
                                           nbins - 1), cond)
    np.testing.assert_array_equal(apps.ncp_profile(g, pvals), want)
    support = apps.support_of(pvals)
    assert support[0].size == np.count_nonzero(pvals > 0)
    np.testing.assert_array_equal(
        apps.ncp_profile(g, pvals, support=support), want)
    # the same vectors delivered as their supports
    np.testing.assert_array_equal(
        apps.ncp_profile(g, SupportRows.from_dense(pvals)), want)


def test_session_ncp_meets_the_acl_bound_and_sweeps_exactly():
    """``FPPSession.ncp`` on a small lattice: every PPR vector within the
    ACL bound of the exact PageRank (0 <= ppr - p < eps * deg on this
    symmetric graph) and of the sequential push oracle, and a profile
    equal to the whole-graph reference sweep of those vectors."""
    from repro.fpp import FPPSession
    g = grid2d(16, 16, seed=4)
    seeds = np.array([0, 37, 130, 255])
    alpha, eps = 0.15, 1e-4
    sess = FPPSession(g).plan(num_queries=len(seeds), block_size=32)
    profile, res = sess.ncp(seeds, alpha=alpha, eps=eps)
    # row-major planes: each seed's row is one contiguous scan
    assert res.values.flags.c_contiguous and res.residual.flags.c_contiguous
    deg = np.maximum(g.out_degree(), 1).astype(np.float64)
    src, dst, _ = g.edges()
    walk = np.zeros((g.n, g.n))
    np.add.at(walk, (dst, src), (1 - alpha) / deg[src])
    exact = alpha * np.linalg.solve(np.eye(g.n) - walk,
                                    np.eye(g.n)[:, seeds]).T
    gap = (exact - res.values) / (eps * deg)
    assert gap.max() < 1.0 and gap.min() > -1e-3
    for qi, s in enumerate(seeds):
        want_p, _, _ = oracles.ppr_push(g, int(s), alpha=alpha, eps=eps)
        assert (np.abs(res.values[qi] - want_p) / deg).max() <= 2 * eps
    nbins = int(np.ceil(np.log2(g.n))) + 1
    want = np.full(nbins, np.inf)
    for p in res.values:
        sizes, cond = oracles.sweep_conductance_ref(g, p)
        np.minimum.at(want, np.minimum(np.log2(sizes).astype(np.int64),
                                       nbins - 1), cond)
    assert np.isfinite(want).sum() >= 4
    np.testing.assert_array_equal(profile, want)
    assert 0 < res.stats["lane_visits"] <= res.stats["visits"] * len(seeds)


@pytest.mark.parametrize("route", ["device", "overflow", "baselines"])
def test_support_delivery_is_the_dense_planes_bit_for_bit(route,
                                                          monkeypatch):
    """``delivery="support"`` hands back the dense run's PPR mass and
    residual on their supports: gathered on the device, read whole when a
    lane outgrows the slots, or taken from another backend's planes; and
    ``sess.ncp`` sweeps it to the same profile."""
    from repro.core import engine
    from repro.fpp import FPPSession
    g = grid2d(16, 16, seed=4)
    seeds = np.array([0, 37, 130, 255])
    sess = FPPSession(g).plan(num_queries=len(seeds), block_size=32)
    kw = {"backend": "baselines"} if route == "baselines" else {}
    if route == "overflow":
        monkeypatch.setattr(engine, "SUPPORT_SLOTS", 8)
    want_profile, want = sess.ncp(seeds, **kw)
    profile, got = sess.ncp(seeds, delivery="support", **kw)
    for plane, dense in ((got.values, want.values),
                         (got.residual, want.residual)):
        assert isinstance(plane, SupportRows) and plane.shape == dense.shape
        assert np.all(np.diff(plane.indices)[np.diff(plane.rows()) == 0] > 0)
        np.testing.assert_array_equal(plane.dense(), dense)
        for qi in range(len(seeds)):
            np.testing.assert_array_equal(plane.row(qi), dense[qi])
    assert got.values.data.size == np.count_nonzero(want.values)
    np.testing.assert_array_equal(profile, want_profile)
    assert got.stats.get("visits") == want.stats.get("visits")


def test_support_delivery_is_for_ppr_only():
    from repro.fpp import FPPSession
    sess = FPPSession(grid2d(6, 6, seed=1)).plan(num_queries=2,
                                                 block_size=16)
    with pytest.raises(ValueError, match="for ppr"):
        sess.run("sssp", np.array([0, 5]), delivery="support")
    with pytest.raises(ValueError, match="unknown delivery"):
        sess.run("ppr", np.array([0, 5]), delivery="sparse")
