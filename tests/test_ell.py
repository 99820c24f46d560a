"""The pull-ELL view of the block store and the min-plus relaxation over it
(``BlockGraph.ell_src`` / ``ell_w``, ``kernels/minplus/ops.minplus_ell``,
DESIGN.md §2.1): the view holds exactly the dense store's values, the
relaxation over it gives the dense relaxation's bits, and the engine
takes the same visit trajectory over either.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import FPPEngine
from repro.core.graph import ELL_MAX_WIDTH_SHARE, BlockGraph, CSRGraph
from repro.core.oracles import kreach_stride
from repro.core.queries import WEIGHT_VARIANTS, prepare, reweight
from repro.core.visit import make_megastep, relax_width
from repro.fpp import FPPSession
from repro.fpp import planner
from repro.graphs.generators import grid2d
from repro.kernels.minplus.ops import minplus_ell
from repro.kernels.minplus.ref import minplus_ref


def _dense_of(src, w, B):
    """The [B, B] tile a pull-ELL slice lists, duplicates folded by min."""
    dense = np.full((B, B), np.inf, np.float32)
    cols = np.broadcast_to(np.arange(B)[:, None], src.shape)
    np.minimum.at(dense, (src, cols), w)
    return dense


def _bits(x):
    return np.asarray(x, dtype=np.float32).view(np.int32)


@pytest.mark.parametrize("width", [1, 3, 8])
@pytest.mark.parametrize("case", ["finite", "inf_and_zero", "empty"])
def test_relax_over_the_view_is_bit_identical_to_the_dense_tile(width,
                                                                 case):
    rng = np.random.default_rng([width, len(case)])
    Q, B = 5, 32
    src = rng.integers(0, B, (B, width)).astype(np.int32)
    w = rng.uniform(0.5, 7.0, (B, width)).astype(np.float32)
    d = rng.uniform(0.0, 20.0, (Q, B)).astype(np.float32)
    if case == "inf_and_zero":
        w[rng.random(w.shape) < 0.3] = 0.0
        w[rng.random(w.shape) < 0.3] = np.inf       # padding slots
        d[rng.random(d.shape) < 0.4] = np.inf
        d[rng.random(d.shape) < 0.2] = 0.0
    elif case == "empty":
        w[::3] = np.inf                             # all-+inf columns
        src[src % 4 == 1] = 0                       # rows no column lists
        d[:, 1::4] = np.inf
        d[2] = np.inf                               # an all-+inf row
    out = minplus_ell(jnp.asarray(d), jnp.asarray(src), jnp.asarray(w))
    ref = minplus_ref(jnp.asarray(d), jnp.asarray(_dense_of(src, w, B)))
    np.testing.assert_array_equal(_bits(out), _bits(ref))


def _duplicate_edges():
    """A ring with chords, every ring edge listed twice at two weights;
    a CSR built without dedup keeps both."""
    n = 300
    u = np.arange(n)
    src = np.concatenate([u, u, u])
    dst = np.concatenate([(u + 1) % n, (u + 1) % n, (u + 7) % n])
    w = np.concatenate([np.full(n, 3.0), np.full(n, 2.0),
                        np.zeros(n)]).astype(np.float32)
    return CSRGraph.from_edges(n, src, dst, w, dedup=False)


@pytest.mark.parametrize("graph", ["lattice", "duplicates"])
def test_view_rebuilds_the_dense_store_exactly(graph):
    g = grid2d(20, 20, seed=2) if graph == "lattice" else _duplicate_edges()
    if graph == "duplicates":
        assert g.m == 900
    bg = BlockGraph.from_csr(g, 128)
    assert 0 < bg.ell_width <= 128 * ELL_MAX_WIDTH_SHARE
    rebuilt = np.stack([_dense_of(s, w, bg.block_size)
                        for s, w in zip(bg.ell_src, bg.ell_w)])
    np.testing.assert_array_equal(_bits(rebuilt), _bits(bg.blocks))
    # the widest column is exactly W: no slot wasted, none missing
    assert np.isfinite(bg.blocks).sum(axis=1).max() == bg.ell_width


def _engine(bg, kind, yc, stride):
    mode = {"sssp": "minplus", "bfs": "minplus"}.get(kind, kind)
    return FPPEngine(bg, mode=mode, num_queries=4, yield_config=yc,
                     hop_budget=3, hop_stride=stride)


@pytest.mark.parametrize("kind", ["sssp", "bfs", "cc", "kreach"])
def test_engine_is_identical_over_the_view_and_the_dense_tile(kind):
    g = grid2d(24, 24, seed=3)
    stride = kreach_stride(g.n, float(g.weights.max()))
    variant = WEIGHT_VARIANTS.get(kind, "natural")
    bg, perm = prepare(reweight(g, variant, stride=stride), 128)
    yc = planner.default_yield_config(kind, bg)
    sources = perm[np.array([0, 77, 300, 575])]

    ell = _engine(bg, kind, yc, stride)
    dense = _engine(bg, kind, yc, stride)
    # the dense twin: the same algebra without its pull-ELL operators
    dense.algebra = dataclasses.replace(dense.algebra, ell=None)
    dense._megastep = make_megastep(dense.dg, dense.algebra,
                                    dense.max_rounds, K=dense.k_visits)
    dense.relax_width = relax_width(dense.dg, dense.algebra)

    a, b = ell.run(sources), dense.run(sources)
    assert a.stats.relax_width == bg.ell_width > 0
    assert b.stats.relax_width == bg.block_size
    np.testing.assert_array_equal(_bits(a.values), _bits(b.values))
    if a.residual is not None:
        np.testing.assert_array_equal(a.residual, b.residual)
    np.testing.assert_array_equal(a.edges_processed, b.edges_processed)
    assert a.stats.visits == b.stats.visits > 0
    assert a.stats.rounds == b.stats.rounds
    np.testing.assert_array_equal(a.stats.visit_counts, b.stats.visit_counts)
    # the per-visit program reads the view too
    c = ell.run(sources, host_loop=True)
    np.testing.assert_array_equal(_bits(c.values), _bits(a.values))
    np.testing.assert_array_equal(c.edges_processed, a.edges_processed)


def _star(n=1025):
    leaves = np.arange(1, n, dtype=np.int64)
    return CSRGraph.from_edges(n, np.zeros(n - 1, np.int64), leaves,
                               symmetrize=True)


@pytest.mark.parametrize("graph,view", [("lattice", True), ("star", False)])
def test_relax_width_says_which_form_ran(graph, view):
    """A lattice's columns hold a few entries, so the run relaxes over the
    view; a hub's column fills a whole block, so it keeps the dense
    tile."""
    g = grid2d(24, 24, seed=1) if graph == "lattice" else _star()
    sess = FPPSession(g).plan(num_queries=2, block_size=128)
    res = sess.run("sssp", np.array([0, 5]))
    bg, _ = sess.prepared()
    assert (bg.ell_width > 0) == view
    assert res.stats["relax_width"] == (bg.ell_width if view else 128)
    if view:
        assert bg.ell_width <= 4
