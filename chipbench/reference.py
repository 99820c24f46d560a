"""Plain references: shortest paths and personalized PageRank.

They import nothing of the program and read only the benchmark's own
edge list (``graphs.EdgeList``).  Each takes the precision it computes in,
so that the control of ``compare.py`` is the same reference one precision
below the configuration's (float32 -> bfloat16).

* ``sssp``: exact distances.  In float64 this is SciPy's Dijkstra; in any
  other precision it is the Bellman-Ford fixpoint with every weight and
  sum rounded to that precision, vectorised over the edge list.
* ``ppr``: the PageRank personalised on a source (or on any start mass)
  with teleport ``alpha`` on the out-degree-normalised walk, by power
  iteration until less than 1e-10 of the walk's mass is left.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra

from chipbench.graphs import EdgeList


def _dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def sssp(edges: EdgeList, sources: np.ndarray,
         precision: str = "float64") -> np.ndarray:
    """[len(sources), n] distances, +inf where unreachable."""
    sources = np.asarray(sources, dtype=np.int64)
    if precision == "float64":
        mat = sp.csr_matrix((edges.weight.astype(np.float64),
                             (edges.src, edges.dst)), shape=(edges.n,) * 2)
        return dijkstra(mat, directed=True, indices=sources)
    # sums are taken in float32 and rounded to ``precision``, as a chip
    # adds in a narrower type
    dt = _dtype(precision)

    def rnd(x):
        return x.astype(dt).astype(np.float32)

    order = np.argsort(edges.dst, kind="stable")
    src, dst = edges.src[order], edges.dst[order]
    w = rnd(edges.weight[order])
    starts = np.flatnonzero(np.r_[True, dst[1:] != dst[:-1]])
    heads = dst[starts]
    d = np.full((sources.size, edges.n), np.inf, dtype=np.float32)
    d[np.arange(sources.size), sources] = 0
    while True:
        best = np.minimum.reduceat(rnd(d[:, src] + w[None, :]), starts,
                                   axis=1)
        new = d.copy()
        new[:, heads] = np.minimum(d[:, heads], best)
        if np.array_equal(new, d):
            return d.astype(np.float64)
        d = new


def walk_iterations(alpha: float, tol: float = 1e-10) -> int:
    """Power-iteration steps after which less than ``tol`` of the walk's
    mass is left: (1 - alpha) ** k < tol."""
    return int(np.ceil(np.log(tol) / np.log(1.0 - alpha)))


def ppr_from(edges: EdgeList, start: np.ndarray, alpha: float,
             precision: str = "float64") -> Tuple[np.ndarray, np.ndarray]:
    """PageRank personalised on each row of ``start`` ([k, n] mass):
    ``(p, r)`` after ``walk_iterations(alpha)`` steps, with ``p + ppr(r)``
    the exact answer and ``r``'s mass below 1e-10 of the start's."""
    dt = _dtype(precision)
    deg = np.maximum(edges.out_degree(), 1).astype(np.float64)
    # column u of the transposed walk spreads u's mass to its out-neighbours
    walk = sp.csr_matrix(((1.0 - alpha) / deg[edges.src],
                          (edges.dst, edges.src)), shape=(edges.n,) * 2)
    if dt != np.float64:
        walk = walk.astype(np.float32)
    y = np.asarray(start, dtype=np.float64).T.astype(dt)
    x = np.zeros_like(y)
    for _ in range(walk_iterations(alpha)):
        x = (x.astype(np.float64) + alpha * y.astype(np.float64)).astype(dt)
        y = np.asarray(walk @ y.astype(walk.dtype)).astype(dt)
    return x.astype(np.float64).T, y.astype(np.float64).T


def ppr(edges: EdgeList, sources: np.ndarray, alpha: float,
        precision: str = "float64") -> Tuple[np.ndarray, np.ndarray]:
    """``ppr_from`` one-hot starts at ``sources``."""
    sources = np.asarray(sources, dtype=np.int64)
    start = np.zeros((sources.size, edges.n))
    start[np.arange(sources.size), sources] = 1.0
    return ppr_from(edges, start, alpha, precision)
