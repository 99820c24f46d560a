"""Hypothesis property tests on the system's invariants."""
import pytest

hypothesis = pytest.importorskip("hypothesis")  # whole module is property-based
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core import oracles
from repro.core.graph import CSRGraph
from repro.core.queries import prepare, run_ppr, run_sssp
from repro.kernels.minplus.ref import minplus_ref
from repro.models.attention import attend
from repro.train.compress import dequantize_int8, quantize_int8

SETTINGS = dict(deadline=None, max_examples=12,
                suppress_health_check=[hypothesis.HealthCheck.too_slow])


@st.composite
def random_graph(draw):
    n = draw(st.integers(24, 96))
    m = draw(st.integers(n, 4 * n))
    seed = draw(st.integers(0, 2 ** 16))
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    w = rng.uniform(1.0, 8.0, m).astype(np.float32)
    keep = src != dst
    return CSRGraph.from_edges(n, src[keep], dst[keep], w[keep],
                               symmetrize=True)


@given(random_graph(), st.integers(0, 2 ** 16))
@settings(**SETTINGS)
def test_sssp_matches_dijkstra_any_graph(g, seed):
    """FPP SSSP == sequential Dijkstra on arbitrary random graphs,
    regardless of the partition layout the graph happens to get."""
    rng = np.random.default_rng(seed)
    srcs = rng.integers(0, g.n, 3)
    bg, perm = prepare(g, 32)
    res = run_sssp(bg, perm[srcs])
    for qi, s in enumerate(srcs):
        want, _ = oracles.dijkstra(g, int(s))
        got = res.values[qi][perm]
        np.testing.assert_allclose(
            np.where(np.isfinite(got), got, -1.0),
            np.where(np.isfinite(want), want, -1.0), rtol=1e-5)


@given(random_graph(), st.integers(0, 2 ** 16))
@settings(**SETTINGS)
def test_ppr_mass_is_conserved(g, seed):
    """p_total + r_total == 1 per query at every point of the push process
    (the buffered execution must not create or destroy probability mass)."""
    rng = np.random.default_rng(seed)
    srcs = rng.integers(0, g.n, 2)
    bg, perm = prepare(g, 32)
    res = run_ppr(bg, perm[srcs], eps=1e-3)
    deg = g.out_degree()
    for qi in range(len(srcs)):
        p = res.values[qi]
        r = res.residual[qi]
        total = float(p.sum() + r.sum())
        # dangling vertices (deg 0) absorb their residual; with symmetrize
        # there are none reachable, so mass is conserved
        np.testing.assert_allclose(total, 1.0, atol=1e-3)


@given(st.integers(0, 2 ** 16), st.integers(1, 4), st.integers(8, 32))
@settings(**SETTINGS)
def test_minplus_is_monotone_and_dominated(seed, q, b):
    """min-plus relaxation never increases distances and is dominated by
    any single-edge path."""
    rng = np.random.default_rng(seed)
    d = jnp.asarray(np.where(rng.random((q, b)) < 0.3, np.inf,
                             rng.uniform(0, 10, (q, b))), jnp.float32)
    w = jnp.asarray(np.where(rng.random((b, b)) < 0.7, np.inf,
                             rng.uniform(0, 5, (b, b))), jnp.float32)
    out = np.asarray(minplus_ref(d, w))
    dn, wn = np.asarray(d), np.asarray(w)
    for qi in range(min(q, 2)):
        for v in range(min(b, 8)):
            want = np.min(dn[qi] + wn[:, v])
            assert out[qi, v] == np.float32(want) or \
                np.isclose(out[qi, v], want, rtol=1e-6)


@given(st.integers(0, 2 ** 16))
@settings(**SETTINGS)
def test_attend_matches_dense_softmax(seed):
    rng = np.random.default_rng(seed)
    B, S, H, hd = 2, 24, 4, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, 2, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, 2, hd)), jnp.float32)
    pos = jnp.arange(S)
    got = attend(q, k, v, pos, pos, causal=True, chunk=8)
    # dense reference
    kr = jnp.repeat(k, 2, axis=2)
    vr = jnp.repeat(v, 2, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / np.sqrt(hd)
    mask = pos[None, :] <= pos[:, None]
    s = jnp.where(mask[None, None], s, -1e9)
    p = jax.nn.softmax(s, axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, vr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


@given(st.lists(st.floats(-100, 100, allow_nan=False), min_size=1,
                max_size=64))
@settings(**SETTINGS)
def test_quantize_bounds(xs):
    x = jnp.asarray(np.asarray(xs, np.float32))
    q, s = quantize_int8(x)
    err = float(jnp.max(jnp.abs(dequantize_int8(q, s) - x)))
    assert err <= float(s) * 0.5 + 1e-6


_FUSED = {}


def _fused_fixture(mode):
    """One engine + fused-visit closure per mode, shared across examples so
    hypothesis varies data, not compilations (shapes stay fixed)."""
    if mode not in _FUSED:
        from repro.core.engine import FPPEngine
        from repro.graphs.generators import grid2d, rmat
        from repro.kernels.frontier.ops import frontier_tile
        from repro.kernels.fused_visit.ops import make_fused_visit
        from repro.kernels.ppr_push.ops import push_tile
        g = grid2d(10, 10, seed=1) if mode == "minplus" else rmat(7, 5,
                                                                  seed=3)
        bg, perm = prepare(g, 32)
        eng = FPPEngine(bg, mode=mode, num_queries=3, fused=True,
                        k_visits=8, eps=1e-3)
        fv = make_fused_visit(eng.dg, eng.algebra, eng.max_rounds,
                              frontier=frontier_tile, push=push_tile)
        fg = fv.graph(eng.dg)
        visit = jax.jit(lambda pk, p, c: fv.visit(fg, pk, p, c))
        _FUSED[mode] = (g, perm, eng, fv, visit)
    return _FUSED[mode]


@given(st.sampled_from(["minplus", "push"]), st.integers(0, 2 ** 16))
@settings(**SETTINGS)
def test_fused_visit_idempotent_on_converged_partitions(mode, seed):
    """Visiting a converged (+inf priority) partition never changes the
    value plane, processes zero edges and zero rounds, and keeps the
    priority empty; and the visit is a bitwise fixed point from the second
    application on.  The first application may only *consolidate* inert
    buffered state — minplus garbage-collects dominated ops (finite buf
    entries above the current distances), push folds sub-threshold
    residual mass from the buffer into r (the ACL terminal condition),
    conserving total mass — neither is visible to the values, the
    priority, or the edge counters.  Convergence is reached by running the
    fused engine itself, so the metadata handed to the kernel is exactly
    what a real run leaves."""
    g, perm, eng, fv, visit = _fused_fixture(mode)
    rng = np.random.default_rng(seed)
    deg = g.out_degree()
    srcs = rng.choice(np.flatnonzero(deg > 0), 3, replace=False)
    state = eng.init_state(perm[srcs])
    key = jax.random.PRNGKey(0)
    counter, limit = 0, eng.k_visits
    for _ in range(10_000):
        state, ms = eng._megastep(state, jnp.int32(counter),
                                  jnp.int32(limit), key)
        v = int(ms.visits)
        counter += v
        if v < limit:
            break
    assert not np.isfinite(np.asarray(state.prio)).any()  # converged
    pk = fv.pack(state.planes, state.buf, state.prio, state.ops_count,
                 state.stamp)
    for p in range(eng.dg.num_parts):
        pk1, rounds, eq = visit(pk, jnp.int32(p), jnp.int32(counter))
        assert int(rounds) == 0
        assert int(np.asarray(eq).sum()) == 0
        planes1, buf1, prio1, _, _ = fv.unpack(pk1)
        assert not np.isfinite(np.asarray(prio1)).any()
        np.testing.assert_array_equal(np.asarray(state.planes[0]),
                                      np.asarray(planes1[0]))
        if mode == "minplus":
            for a, b in zip(state.planes, planes1):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            P = eng.dg.num_parts
            mass0 = sum(np.asarray(x, np.float64).sum()
                        for x in (*state.planes, state.buf[:P]))
            mass1 = sum(np.asarray(x, np.float64).sum()
                        for x in (*planes1, buf1[:P]))
            np.testing.assert_allclose(mass1, mass0, atol=1e-5)
        # second application: a bitwise fixed point of the whole packed
        # state, scheduler metadata included
        pk2, rounds2, eq2 = visit(pk1, jnp.int32(p), jnp.int32(counter))
        assert int(rounds2) == 0
        assert int(np.asarray(eq2).sum()) == 0
        for a, b in zip(pk1, pk2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(1, 200), st.sampled_from([8, 16, 64]),
       st.integers(0, 2 ** 16))
@settings(**SETTINGS)
def test_pad_q_identity_padding_is_invisible(q, q_tile, seed):
    """``_pad_q`` pads the query axis with the mode identity so the kernel
    can demand exact tile divisibility; at ANY Q — divisible or not — the
    padded rows must be inert: min-plus bitwise equal to the unpadded ref
    (+inf sources contribute only +inf candidates), the masked matmul
    row-independent (zero rows spread nothing)."""
    from repro.kernels.minplus.ops import (masked_matmul_pallas,
                                           minplus_pallas)
    from repro.kernels.minplus.ref import masked_matmul_ref
    rng = np.random.default_rng(seed)
    b = 32
    d = jnp.asarray(np.where(rng.random((q, b)) < 0.4, np.inf,
                             rng.uniform(0, 9, (q, b))), jnp.float32)
    w = jnp.asarray(np.where(rng.random((b, b)) < 0.7, np.inf,
                             rng.uniform(0, 5, (b, b))), jnp.float32)
    got = minplus_pallas(d, w, q_tile=q_tile)
    want = minplus_ref(d, w)
    assert got.shape == (q, b)
    np.testing.assert_array_equal(
        np.nan_to_num(np.asarray(got), posinf=1e30),
        np.nan_to_num(np.asarray(want), posinf=1e30))
    x = jnp.asarray(rng.uniform(0, 1, (q, b)), jnp.float32)
    got_mm = masked_matmul_pallas(x, w, q_tile=q_tile)
    assert got_mm.shape == (q, b)
    np.testing.assert_allclose(np.asarray(got_mm),
                               np.asarray(masked_matmul_ref(x, w)),
                               atol=1e-6)


@given(random_graph())
@settings(**SETTINGS)
def test_schedule_policies_agree_on_results(g):
    """All four scheduling policies produce identical SSSP distances —
    scheduling affects work, never correctness (paper §5)."""
    rng = np.random.default_rng(0)
    srcs = rng.integers(0, g.n, 2)
    bg, perm = prepare(g, 32)
    outs = {}
    for pol in ("priority", "fifo", "random", "max_ops"):
        res = run_sssp(bg, perm[srcs], schedule=pol)
        outs[pol] = np.where(np.isfinite(res.values), res.values, -1.0)
    base = outs["priority"]
    for pol, v in outs.items():
        np.testing.assert_allclose(v, base, rtol=1e-5, err_msg=pol)
