"""Unit + property tests for CSR / BlockGraph containers."""
import warnings

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:        # degrade: unit tests run, property tests skip
    given = None

from repro.core.graph import BlockGraph, CSRGraph, vmem_block_size
from repro.core.queries import reweight
from repro.core.yielding import default_delta
from repro.fpp.planner import default_yield_config
from repro.graphs.generators import erdos_renyi, grid2d, rmat, watts_strogatz


def test_csr_from_edges_dedup_minweight():
    # duplicate edge keeps the min weight; self loops dropped
    g = CSRGraph.from_edges(4, [0, 0, 1, 2, 2], [1, 1, 1, 3, 3],
                            [5.0, 2.0, 9.9, 1.0, 7.0])
    assert g.m == 2
    src, dst, w = g.edges()
    assert list(src) == [0, 2] and list(dst) == [1, 3]
    assert np.allclose(w, [2.0, 1.0])


def test_csr_permute_preserves_edges():
    g = grid2d(5, 5, seed=0)
    perm = np.random.default_rng(0).permutation(g.n)
    gp = g.permute(perm)
    s0, d0, w0 = g.edges()
    s1, d1, w1 = gp.edges()
    e0 = {(int(perm[a]), int(perm[b]), round(float(c), 5))
          for a, b, c in zip(s0, d0, w0)}
    e1 = {(int(a), int(b), round(float(c), 5)) for a, b, c in zip(s1, d1, w1)}
    assert e0 == e1


@pytest.mark.parametrize("gen", [
    lambda: grid2d(7, 9, seed=1),
    lambda: rmat(7, 4, seed=2),
    lambda: erdos_renyi(100, 3.0, seed=3),
    lambda: watts_strogatz(80, 6, 0.3, seed=4),
])
@pytest.mark.parametrize("block_size", [16, 64])
def test_blockgraph_roundtrip(gen, block_size):
    """Every CSR edge appears in exactly one dense block with its weight."""
    g = gen()
    bg = BlockGraph.from_csr(g, block_size)
    B = bg.block_size
    src, dst, w = g.edges()
    recon = {}
    for k in range(bg.blocks.shape[0]):
        us, vs = np.nonzero(np.isfinite(bg.blocks[k]))
        for u, v in zip(us, vs):
            gu = int(bg.blk_src[k]) * B + int(u)
            gv = int(bg.blk_dst[k]) * B + int(v)
            recon[(gu, gv)] = float(bg.blocks[k, u, v])
    expect = {(int(a), int(b)): float(c) for a, b, c in zip(src, dst, w)}
    assert recon == pytest.approx(expect)
    # degree bookkeeping matches CSR
    assert (bg.deg.reshape(-1)[:g.n] == g.out_degree()).all()
    assert bg.vmask.sum() == g.n
    # row_nnz consistent with blocks
    assert (bg.row_nnz == np.isfinite(bg.blocks).sum(axis=2)).all()


def test_blockgraph_diagonal_always_present():
    g = CSRGraph.from_edges(10, [0], [9], [1.0])  # only a cross-block edge
    bg = BlockGraph.from_csr(g, 4)
    assert len(bg.diag_blk) == bg.num_parts
    for p in range(bg.num_parts):
        k = bg.diag_blk[p]
        assert bg.blk_src[k] == p and bg.blk_dst[k] == p


def _scanned_weight_max(bg: BlockGraph) -> float:
    """The Δ-window's scale read the way it was before the BlockGraph kept
    it: a scan of the whole block store."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # all-NaN: no edge
        return float(np.nanmax(np.where(np.isfinite(bg.blocks), bg.blocks,
                                        np.nan)))


def _heavy_duplicate() -> CSRGraph:
    # two parallel 0 -> 1 edges: the heavier is the largest weight given,
    # but the store folds the pair to the lighter, so the maximum is 5
    return CSRGraph.from_edges(6, [0, 0, 2, 3], [1, 1, 3, 4],
                               [2.0, 9.0, 5.0, 1.5], dedup=False)


WEIGHT_MAX_GRAPHS = {
    "grid2d": lambda: grid2d(7, 9, seed=1),
    "heavy-duplicate": _heavy_duplicate,
    **{f"reweight-{v}": (lambda v=v: reweight(grid2d(6, 6, seed=2), v))
       for v in ("natural", "unit", "zero", "shift")},
    "edgeless": lambda: CSRGraph.from_edges(5, [], []),
}


@pytest.mark.parametrize("name", list(WEIGHT_MAX_GRAPHS))
def test_weight_max_is_the_block_store_scan_bit_for_bit(name):
    g = WEIGHT_MAX_GRAPHS[name]()
    bg = BlockGraph.from_csr(g, 4)
    scanned = _scanned_weight_max(bg)
    assert (np.float64(bg.weight_max).tobytes()
            == np.float64(scanned).tobytes())
    assert default_yield_config("sssp", bg).delta == default_delta(scanned)
    if name == "heavy-duplicate":
        assert bg.weight_max == 5.0 < g.weights.max()


def test_vmem_block_size_monotone():
    assert vmem_block_size(16 << 20) <= vmem_block_size(128 << 20)
    b = vmem_block_size(96 << 20, num_queries=256)
    assert 2 * b * b * 4 + 2 * 256 * b * 4 <= 96 << 20


if given is not None:
    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 23), st.integers(0, 23)),
                    min_size=1, max_size=60))
    def test_blockgraph_roundtrip_property(edges):
        src = np.array([e[0] for e in edges])
        dst = np.array([e[1] for e in edges])
        g = CSRGraph.from_edges(24, src, dst)
        bg = BlockGraph.from_csr(g, 8)
        # every finite entry corresponds to a real edge and vice versa
        total = int(np.isfinite(bg.blocks).sum())
        assert total == g.m
else:
    def test_blockgraph_roundtrip_property():
        pytest.importorskip("hypothesis")
