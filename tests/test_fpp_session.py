"""FPPSession front door: planning, backend agreement, streaming.

The session contract under test (DESIGN.md §3):
  * the planner's block size fits the device memory model;
  * the same query set through engine / distributed / baselines matches
    core/oracles.py, with identical result dtypes and shapes;
  * a staggered-arrival streaming run returns the same answers as the
    one-shot run of the union.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import oracles
from repro.fpp import FPPSession, MemoryModel
from repro.fpp.planner import model_block_size
from repro.graphs.generators import grid2d, rmat


# ---------------------------------------------------------------- planning


def test_planner_block_size_fits_memory_model():
    g = grid2d(32, 32, seed=0)
    for vmem in (1 << 20, 8 << 20, 96 << 20):
        mem = MemoryModel(vmem_bytes=vmem)
        b = model_block_size(g, num_queries=64, mem=mem)
        assert mem.working_set(b, 64) <= vmem
    # tighter budget can never pick a larger block
    assert (model_block_size(g, 64, MemoryModel(vmem_bytes=1 << 20))
            <= model_block_size(g, 64, MemoryModel(vmem_bytes=96 << 20)))


def test_planner_keeps_enough_partitions():
    g = grid2d(12, 12, seed=1)           # 144 vertices
    b = model_block_size(g, 4, MemoryModel())
    assert -(-g.n // b) >= 2             # never collapses to one partition


class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_memory_model_is_keyed_by_device_kind():
    """A TPU gets its table row (the VMEM limit the kernels request, the
    chip's HBM); an unknown TPU kind raises instead of guessing; other
    backends keep the explicit defaults."""
    from repro.kernels.tpu import TPU_MEMORY
    v5e = MemoryModel.for_device(_Device("tpu", "TPU v5 lite"))
    row = TPU_MEMORY["TPU v5 lite"]
    assert (v5e.vmem_bytes, v5e.hbm_bytes) == (row.vmem_limit_bytes,
                                               row.hbm_bytes)
    with pytest.raises(ValueError, match="TPU v99"):
        MemoryModel.for_device(_Device("tpu", "TPU v99"))
    assert MemoryModel.for_device(_Device("cpu", "cpu")) == MemoryModel()


def test_road_graph_plan_fits_v5e():
    """The chip smoke graph (grid2d 512x512, Q=64) on v5e: the plan's B
    keeps the fused visit inside the kernels' VMEM limit and the state
    plus the dense blocks inside HBM — 1024, where 2048's fused visit
    would not fit VMEM."""
    from repro.fpp.planner import FUSED_DMAX_BUDGET, est_dmax, make_plan
    g = grid2d(512, 512, seed=0)
    mem = MemoryModel.for_device(_Device("tpu", "TPU v5 lite"))
    b = make_plan(g, 64, mem=mem).block_size
    assert b == 1024
    assert mem.fused_working_set(b, 64, 2, FUSED_DMAX_BUDGET) \
        <= mem.vmem_bytes
    assert not mem.fits(2 * b, 64, g.n, fused=True)
    blocks = -(-g.n // b) * (1 + est_dmax(g, b)) * b * b * 4
    assert mem.state_bytes(g.n, 64, b) + blocks <= mem.hbm_bytes


def test_plan_tune_measures_and_picks_feasible():
    g = grid2d(16, 16, seed=2)
    srcs = np.array([0, 100, 200, 255])
    sess = FPPSession(g).plan(num_queries=4, tune=True, tune_sources=srcs)
    plan = sess.current_plan
    assert plan.tuned and len(plan.tuning_rows) >= 1
    assert plan.mem.fits(plan.block_size, 4, g.n)
    # the tuned pick minimizes the recorded traffic objective
    rows = [dict(r) for r in plan.tuning_rows]
    best = min(rows, key=lambda r: (r["traffic_bytes"], r["runtime_s"]))
    assert plan.block_size == best["block_size"]


# ------------------------------------------------------- backend agreement


def _oracle_sssp(g, srcs):
    return np.stack([oracles.dijkstra(g, int(s))[0] for s in srcs])


def test_engine_and_baselines_match_oracles_same_contract():
    g = grid2d(12, 12, seed=3)
    srcs = np.array([0, 70, 143, 5])
    want = _oracle_sssp(g, srcs)
    sess = FPPSession(g).plan(num_queries=len(srcs), block_size=32)
    for backend in ("engine", "baselines"):
        res = sess.run("sssp", srcs, backend=backend)
        assert res.values.dtype == np.float32          # identical dtypes
        assert res.values.shape == (len(srcs), g.n)    # identical shapes
        assert res.edges_processed.dtype == np.float64
        assert res.edges_processed.shape == (len(srcs),)
        np.testing.assert_allclose(
            np.nan_to_num(res.values, posinf=1e30),
            np.nan_to_num(want, posinf=1e30), atol=1e-3)


def test_bfs_both_backends_match_oracle():
    g = rmat(7, 4, seed=4, weighted=False)
    srcs = np.array([0, 17, 90])
    sess = FPPSession(g).plan(num_queries=len(srcs), block_size=32)
    for backend in ("engine", "baselines"):
        res = sess.run("bfs", srcs, backend=backend)
        for qi, s in enumerate(srcs):
            want, _ = oracles.bfs(g, int(s))
            got = np.where(np.isfinite(res.values[qi]),
                           res.values[qi], -1).astype(np.int32)
            assert (got == want).all(), (backend, qi)


def test_ppr_backends_contract_and_accuracy():
    """Three-way PPR parity: engine vs distributed vs baselines, all against
    the sequential ACL oracle with the same tolerance (one visit algebra)."""
    g = rmat(7, 6, seed=5)
    deg = g.out_degree()
    srcs = np.random.default_rng(0).choice(np.flatnonzero(deg > 0), 3,
                                           replace=False)
    eps = 1e-4
    sess = FPPSession(g).plan(num_queries=len(srcs), block_size=32)
    outs = {}
    for backend in ("engine", "distributed", "baselines"):
        res = sess.run("ppr", srcs, backend=backend, eps=eps)
        assert res.values.dtype == np.float32
        assert res.values.shape == (len(srcs), g.n)
        assert res.residual is not None and res.residual.dtype == np.float32
        outs[backend] = res
    for qi, s in enumerate(srcs):
        want_p, _, _ = oracles.ppr_push(g, int(s), eps=eps)
        for backend, res in outs.items():
            err = np.abs(res.values[qi] - want_p) / np.maximum(deg, 1)
            assert err.max() <= 2 * eps, (backend, qi)
            if backend != "baselines":   # Jacobi baseline reports residual=0
                # buffered runtimes conserve p + r mass exactly
                mass = res.values[qi].sum() + res.residual[qi].sum()
                assert abs(mass - 1.0) < 5e-3, (backend, qi)


def test_every_backend_kind_pair_dispatches():
    """No (backend, kind) combination raises — the visit algebra serves both
    families on every execution path (ISSUE 3 acceptance)."""
    from repro.fpp.backends import BACKENDS, KINDS
    g = grid2d(8, 8, seed=9)
    srcs = np.array([0, 63])
    sess = FPPSession(g).plan(num_queries=len(srcs), block_size=16)
    for backend in BACKENDS:
        for kind in KINDS:
            res = sess.run(kind, srcs, backend=backend, eps=1e-3)
            assert res.values.shape == (len(srcs), g.n), (backend, kind)
            assert res.edges_processed.dtype == np.float64, (backend, kind)
            # counts are exact integers, not drifted float32 sums
            assert (res.edges_processed
                    == np.round(res.edges_processed)).all(), (backend, kind)


_DISTRIBUTED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    from repro.core import oracles
    from repro.fpp import FPPSession
    from repro.graphs.generators import grid2d

    g = grid2d(12, 12, seed=3)
    srcs = np.array([0, 70, 143, 5])
    sess = FPPSession(g).plan(num_queries=len(srcs), block_size=32)
    res = sess.run("sssp", srcs, backend="distributed")
    assert res.values.dtype == np.float32, res.values.dtype
    assert res.values.shape == (len(srcs), g.n), res.values.shape
    assert res.edges_processed.dtype == np.float64
    for qi, s in enumerate(srcs):
        want, _ = oracles.dijkstra(g, int(s))
        np.testing.assert_allclose(np.nan_to_num(res.values[qi], posinf=1e30),
                                   np.nan_to_num(want, posinf=1e30), atol=1e-3)
    assert res.stats["supersteps"] > 0

    # push kind through the same distributed path (same algebra, + not min)
    eps = 1e-3
    deg = np.maximum(g.out_degree(), 1)
    pres = sess.run("ppr", srcs, backend="distributed", eps=eps)
    assert pres.values.dtype == np.float32 and pres.residual is not None
    for qi, s in enumerate(srcs):
        want_p, _, _ = oracles.ppr_push(g, int(s), eps=eps)
        err = np.abs(pres.values[qi] - want_p) / deg
        assert err.max() <= 2 * eps, (qi, float(err.max()))
    print("SESSION_DISTRIBUTED_OK")
""")


def test_distributed_backend_matches_oracles_eight_device_mesh():
    """Same queries (sssp AND ppr) through the shard_map runtime on a
    forced-8-device CPU mesh — the ISSUE 3 acceptance configuration.

    Subprocess because the host-platform device-count flag must be set
    before jax initializes (same pattern as tests/test_distributed.py).
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _DISTRIBUTED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SESSION_DISTRIBUTED_OK" in out.stdout


# ----------------------------------------------------------------- stream


def test_streaming_staggered_matches_one_shot():
    g = grid2d(12, 12, seed=6)
    srcs = np.array([0, 40, 80, 120, 143, 7])
    sess = FPPSession(g).plan(num_queries=len(srcs), block_size=32)
    one = sess.run("sssp", srcs)
    # capacity below the union size forces admission-queue + lane recycling
    stream = sess.stream("sssp", capacity=4)
    first = stream.submit(srcs[:3])
    stream.pump(3)                        # in-flight work between arrivals
    second = stream.submit(srcs[3:])
    out = stream.run()
    assert len(out) == len(srcs)
    for i, qid in enumerate(first + second):
        q = stream.result(qid)
        assert q.done and q.values.dtype == np.float32
        np.testing.assert_array_equal(out[qid], one.values[i])


def test_streaming_ppr_staggered_matches_one_shot_union():
    """The push twin of the minplus staggered-vs-one-shot property: late
    arrivals answer within the same eps tolerance the one-shot union carries
    (push visit order affects rounding, not the ACL guarantee)."""
    g = grid2d(10, 10, seed=11)
    deg = g.out_degree()
    srcs = np.array([0, 33, 55, 77, 99])
    eps = 1e-3
    sess = FPPSession(g).plan(num_queries=len(srcs), block_size=32)
    one = sess.run("ppr", srcs, eps=eps)
    # capacity below the union size forces admission-queue + lane recycling
    stream = sess.stream("ppr", capacity=3, eps=eps)
    first = stream.submit(srcs[:2])
    stream.pump(3)                        # in-flight work between arrivals
    second = stream.submit(srcs[2:])
    out = stream.run()
    assert len(out) == len(srcs)
    degc = np.maximum(deg, 1)
    for i, qid in enumerate(first + second):
        q = stream.result(qid)
        assert q.done and q.values.dtype == np.float32
        # each run sits within 2eps of the truth, so mutually within 4eps
        diff = np.abs(out[qid] - one.values[i]) / degc
        assert diff.max() <= 4 * eps, (i, diff.max())
        mass = q.values.sum() + q.residual.sum()
        assert abs(mass - 1.0) < 5e-3, i


def test_streaming_ppr_invariants():
    g = grid2d(10, 10, seed=7)
    srcs = np.array([0, 55, 99])
    eps = 1e-3
    sess = FPPSession(g).plan(num_queries=len(srcs), block_size=32)
    stream = sess.stream("ppr", capacity=2, eps=eps)
    qids = stream.submit(srcs[:2])
    stream.pump(2)
    qids += stream.submit(srcs[2:])
    out = stream.run()
    deg = g.out_degree()
    for qid, s in zip(qids, srcs):
        q = stream.result(qid)
        # mass conservation and the ACL terminal condition hold per lane
        assert abs(q.values.sum() + q.residual.sum() - 1.0) < 5e-3
        assert (q.residual <= eps * np.maximum(deg, 1) + 1e-6).all()
        want_p, _, _ = oracles.ppr_push(g, int(s), eps=eps)
        err = np.abs(q.values - want_p) / np.maximum(deg, 1)
        assert err.max() <= 2 * eps


def test_streaming_empty_run_terminates():
    g = grid2d(6, 6, seed=8)
    sess = FPPSession(g).plan(num_queries=2, block_size=16)
    stream = sess.stream("sssp", capacity=2)
    assert stream.run() == {}
    assert stream.visits == 0
