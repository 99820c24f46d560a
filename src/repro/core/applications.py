"""FPP-based applications from the paper: BC, NCP, LL (§1, §6.1).

Per the paper, the FPP phase (the batched graph queries) dominates (>90%) and
runs on the buffered engine; the per-application gather phases (Brandes
accumulation, conductance sweeps, label assembly) are host-side numpy.

The query phase goes through the unified ``FPPSession`` front door
(fpp/session.py, DESIGN.md §3); the gather phases are exposed standalone
(``bc_accumulate``, ``ncp_profile``) so the session's application methods and
these legacy entry points share one implementation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.core.graph import CSRGraph
from repro.core.support import SupportRows
from repro.core.yielding import YieldConfig


def _session(g: CSRGraph, block_size: int, method: str,
             schedule: str, yield_config: Optional[YieldConfig],
             num_queries: int):
    from repro.fpp.session import FPPSession   # lazy: avoid import cycle
    return FPPSession(g).plan(num_queries=num_queries, block_size=block_size,
                              method=method, schedule=schedule,
                              yield_config=yield_config)


# ---------------------------------------------------------------------------
# Betweenness centrality (Brandes with sampled sources, Eppstein-style approx)


def _sigma_delta(g: CSRGraph, dist: np.ndarray):
    """Vectorized-by-level Brandes counting for one source's BFS ``dist``
    (int levels, -1 unreachable). Returns (sigma, delta)."""
    src, dst, _ = g.edges()
    sigma = np.zeros(g.n, dtype=np.float64)
    delta = np.zeros(g.n, dtype=np.float64)
    if (dist >= 0).sum() == 0:
        return sigma, delta
    root = int(np.flatnonzero(dist == 0)[0])
    sigma[root] = 1.0
    maxlev = int(dist.max())
    tree = (dist[src] >= 0) & (dist[dst] == dist[src] + 1)
    tsrc, tdst = src[tree], dst[tree]
    lev_of_edge = dist[tdst]  # level of the deeper endpoint
    for lev in range(1, maxlev + 1):
        sel = lev_of_edge == lev
        np.add.at(sigma, tdst[sel], sigma[tsrc[sel]])
    for lev in range(maxlev, 0, -1):
        sel = lev_of_edge == lev
        contrib = (sigma[tsrc[sel]] / np.maximum(sigma[tdst[sel]], 1.0)
                   * (1.0 + delta[tdst[sel]]))
        np.add.at(delta, tsrc[sel], contrib)
    return sigma, delta


def bc_accumulate(g: CSRGraph, sources: np.ndarray,
                  levels: np.ndarray) -> np.ndarray:
    """Brandes gather phase over per-source BFS levels (original ids).

    ``levels``: float [Q, n], +inf (or any non-finite) = unreachable.
    """
    bc = np.zeros(g.n, dtype=np.float64)
    for qi, s in enumerate(np.asarray(sources)):
        lev = levels[qi]
        lev = np.where(np.isfinite(lev), lev, -1).astype(np.int32)
        _, delta = _sigma_delta(g, lev)
        delta[s] = 0.0
        bc += delta
    return bc


def betweenness_centrality(g: CSRGraph, sources: np.ndarray,
                           block_size: int = 256, method: str = "bfs",
                           yield_config: Optional[YieldConfig] = None,
                           schedule: str = "priority"):
    """Approximate BC by |sources| sampled BFS roots (paper: 100 random)."""
    sess = _session(g, block_size, method, schedule, yield_config,
                    len(np.asarray(sources)))
    bc, res = sess.bc(np.asarray(sources))
    return bc, res


# ---------------------------------------------------------------------------
# Landmark labeling


@dataclasses.dataclass
class LandmarkLabels:
    landmarks: np.ndarray   # [L]
    dists: np.ndarray       # [L, n] distances from each landmark

    def query(self, u, v) -> np.ndarray:
        """Upper-bound distance estimate via best landmark (paper's LL use)."""
        return np.min(self.dists[:, u] + self.dists[:, v], axis=0)


def landmark_labeling(g: CSRGraph, landmarks: np.ndarray,
                      block_size: int = 256, method: str = "bfs",
                      yield_config: Optional[YieldConfig] = None,
                      schedule: str = "priority"):
    """Batch-of-SSSPs labeling (paper follows Akiba et al.: 16..1024 SSSPs)."""
    sess = _session(g, block_size, method, schedule, yield_config,
                    len(np.asarray(landmarks)))
    return sess.landmarks(np.asarray(landmarks))


# ---------------------------------------------------------------------------
# Network community profile (via many PPRs + sweep cuts)


def support_of(pvals):
    """The supports ``{v : p(v) > 0}`` of the rows of ``pvals`` — a
    ``[Q, n]`` array (one scan of the whole batch) or
    :class:`~repro.core.support.SupportRows` — as ``(flat, p)``: ascending
    flat indices ``row * n + v`` and the values there."""
    if isinstance(pvals, SupportRows):
        keep = pvals.data > 0
        return ((pvals.rows() * pvals.n + pvals.indices)[keep],
                pvals.data[keep])
    flat = np.flatnonzero(pvals > 0)
    return flat, pvals[np.divmod(flat, pvals.shape[1])]


def _sweeps(g: CSRGraph, shape, flat: np.ndarray, p: np.ndarray):
    """Every row's sweep at once, reading only the supports (``flat`` and
    ``p``, from :func:`support_of`) and their CSR rows: O(vol(supports)
    log).

    Returns ``(sizes, cond)``, one entry per sweep prefix of each row whose
    support holds two vertices or more, rows ascending and each row in
    sweep order.  Volumes and cuts are integer-valued float64 sums, so
    they are exact whatever order they are added in."""
    q, n = shape
    if flat.size == 0:
        return np.array([], dtype=np.int64), np.array([])
    row, v = np.divmod(flat, n)
    counts = np.bincount(row, minlength=q)
    start = np.cumsum(counts) - counts          # first slot of each row
    lo, lens = g.indptr[v], g.indptr[v + 1] - g.indptr[v]
    deg = lens.astype(np.float64)
    score = p / np.maximum(deg, 1.0)
    # sweep order: by row, then by -p/deg; lexsort is stable, so ties keep
    # ascending vertex ids
    by_score = np.lexsort((-score, row))
    rank = np.empty(flat.size, dtype=np.int64)  # slot -> sweep position
    rank[by_score] = np.arange(flat.size)
    # each support vertex's out-arcs: a head in the same row's support is
    # an internal arc, counted at the later of its two sweep positions
    tail = np.repeat(np.arange(flat.size), lens)
    first = np.cumsum(lens) - lens
    key = row[tail] * n + g.indices[np.arange(tail.size)
                                    + np.repeat(lo - first, lens)]
    slot = np.minimum(np.searchsorted(flat, key), flat.size - 1)
    inside = flat[slot] == key
    eranks = np.maximum(rank[tail[inside]], rank[slot[inside]])
    internal = np.bincount(eranks, minlength=flat.size).astype(np.float64)

    def within_row(x):                          # per-row running sums
        run = np.cumsum(x)
        before = np.where(start > 0, run[start - 1], 0.0)
        return run - np.repeat(before, counts)

    vol = within_row(deg[by_score])
    cut = vol - within_row(internal)
    m2 = float(g.indptr[-1])
    denom = np.minimum(vol, m2 - vol)
    keep = denom > 0
    cond = np.full(flat.size, np.inf)
    cond[keep] = cut[keep] / denom[keep]
    sorted_row = row[by_score]
    sizes = np.arange(flat.size) - start[sorted_row] + 1
    swept = counts[sorted_row] >= 2
    return sizes[swept], cond[swept]


def sweep_conductance(g: CSRGraph, p: np.ndarray):
    """Sweep cut over one PPR vector. Returns (sizes, conductances) along the
    sweep prefix order (deg-normalized, ACL standard): the support ordered
    by ``-p/deg``, ties in ascending vertex id.  Bit for bit
    ``oracles.sweep_conductance_ref``, which scans the whole graph."""
    p = np.asarray(p)[None]
    return _sweeps(g, p.shape, *support_of(p))


def ncp_profile(g: CSRGraph, pvals, max_size: Optional[int] = None, *,
                support=None) -> np.ndarray:
    """Min conductance per log2 cluster-size bin over PPR vectors: a
    ``[Q, n]`` array or :class:`~repro.core.support.SupportRows`.

    Each row costs its support's volume, not O(n + m): ``support`` is
    :func:`support_of` ``(pvals)``, made here when the caller has none."""
    max_size = max_size or g.n
    nbins = int(np.ceil(np.log2(max_size))) + 1
    best = np.full(nbins, np.inf)
    if support is None:
        support = support_of(pvals)
    sizes, cond = _sweeps(g, pvals.shape, *support)
    bins = np.minimum(np.log2(sizes).astype(np.int64), nbins - 1)
    np.minimum.at(best, bins, cond)
    return best


def ncp(g: CSRGraph, seeds: np.ndarray, alpha: float = 0.15,
        eps: float = 1e-4, block_size: int = 256, method: str = "bfs",
        yield_config: Optional[YieldConfig] = None,
        schedule: str = "priority", max_size: Optional[int] = None):
    """Network community profile: min conductance per cluster size (log bins).

    Paper setting: PPRs seeded from 0.01% random vertices (we take ``seeds``)."""
    sess = _session(g, block_size, method, schedule, yield_config,
                    len(np.asarray(seeds)))
    return sess.ncp(np.asarray(seeds), alpha=alpha, eps=eps,
                    max_size=max_size)
