"""Golden sequential references — the paper's work-efficiency yardsticks.

Dijkstra (binary heap) for SSSP, deque BFS, Andersen-Chung-Lang push for PPR,
and an explicit-stack DFS (host-only; see DESIGN.md §2 — DFS has no
data-parallel TPU mapping).  Each oracle also reports ``edges_processed`` so
benchmarks can compute the paper's work ratios (Fig. 10 / Appendix A).
"""
from __future__ import annotations

import heapq
from collections import deque
from typing import Dict, Tuple

import numpy as np

from repro.core.graph import CSRGraph


def dijkstra(g: CSRGraph, src: int) -> Tuple[np.ndarray, int]:
    dist = np.full(g.n, np.inf, dtype=np.float64)
    dist[src] = 0.0
    done = np.zeros(g.n, dtype=bool)
    heap = [(0.0, src)]
    edges = 0
    while heap:
        d, u = heapq.heappop(heap)
        if done[u]:
            continue
        done[u] = True
        for e in range(g.indptr[u], g.indptr[u + 1]):
            v = int(g.indices[e])
            edges += 1
            nd = d + float(g.weights[e])
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(heap, (nd, v))
    return dist.astype(np.float32), edges


def bfs(g: CSRGraph, src: int) -> Tuple[np.ndarray, int]:
    dist = np.full(g.n, -1, dtype=np.int32)
    dist[src] = 0
    dq = deque([src])
    edges = 0
    while dq:
        u = dq.popleft()
        for e in range(g.indptr[u], g.indptr[u + 1]):
            v = int(g.indices[e])
            edges += 1
            if dist[v] < 0:
                dist[v] = dist[u] + 1
                dq.append(v)
    return dist, edges


def bfs_sigma(g: CSRGraph, src: int) -> Tuple[np.ndarray, np.ndarray, int]:
    """BFS distances + shortest-path counts (for Brandes BC)."""
    dist = np.full(g.n, -1, dtype=np.int32)
    sigma = np.zeros(g.n, dtype=np.float64)
    dist[src] = 0
    sigma[src] = 1.0
    dq = deque([src])
    edges = 0
    while dq:
        u = dq.popleft()
        for e in range(g.indptr[u], g.indptr[u + 1]):
            v = int(g.indices[e])
            edges += 1
            if dist[v] < 0:
                dist[v] = dist[u] + 1
                dq.append(v)
            if dist[v] == dist[u] + 1:
                sigma[v] += sigma[u]
    return dist, sigma, edges


def ppr_push(g: CSRGraph, src: int, alpha: float = 0.15,
             eps: float = 1e-4) -> Tuple[np.ndarray, np.ndarray, int]:
    """Sequential ACL push (the paper reuses Shun et al. [54]'s version).

    Invariant maintained: p + alpha-smoothed residual approximates the PPR
    vector; terminates when all residuals r[u] < eps * deg(u).
    """
    deg = np.maximum(g.out_degree(), 1).astype(np.float64)
    p = np.zeros(g.n, dtype=np.float64)
    r = np.zeros(g.n, dtype=np.float64)
    r[src] = 1.0
    edges = 0
    queue = deque([src])
    inq = np.zeros(g.n, dtype=bool)
    inq[src] = True
    while queue:
        u = queue.popleft()
        inq[u] = False
        ru = r[u]
        if ru < eps * deg[u]:
            continue
        p[u] += alpha * ru
        push = (1.0 - alpha) * ru / deg[u]
        r[u] = 0.0
        for e in range(g.indptr[u], g.indptr[u + 1]):
            v = int(g.indices[e])
            edges += 1
            r[v] += push
            if r[v] >= eps * deg[v] and not inq[v]:
                inq[v] = True
                queue.append(v)
    return p.astype(np.float32), r.astype(np.float32), edges


def sweep_conductance_ref(g: CSRGraph, p: np.ndarray):
    """Whole-graph sweep cut over one PPR vector, the plain reference of
    ``applications.sweep_conductance``: it ranks all n vertices and scans
    every arc.  Returns (sizes, conductances) along the sweep prefix order
    (deg-normalized, ACL standard)."""
    deg = g.out_degree().astype(np.float64)
    support = np.flatnonzero(p > 0)
    if support.size < 2:
        return np.array([], dtype=np.int64), np.array([])
    score = p[support] / np.maximum(deg[support], 1.0)
    order = support[np.argsort(-score, kind="stable")]
    rank = np.full(g.n, np.iinfo(np.int64).max, dtype=np.int64)
    rank[order] = np.arange(order.size)
    vol = np.cumsum(deg[order])
    src, dst, _ = g.edges()
    both = (rank[src] < order.size) & (rank[dst] < order.size)
    eranks = np.maximum(rank[src[both]], rank[dst[both]])
    internal = np.bincount(eranks, minlength=order.size).astype(np.float64)
    cut = vol - np.cumsum(internal)
    m2 = float(deg.sum())
    denom = np.minimum(vol, m2 - vol)
    keep = denom > 0
    cond = np.full(order.size, np.inf)
    cond[keep] = cut[keep] / denom[keep]
    sizes = np.arange(1, order.size + 1)
    return sizes, cond


def connected_components(g: CSRGraph) -> np.ndarray:
    """Union-find component labels; label = min vertex id in the component.

    The differential anchor for the ``cc`` kind: min-label propagation over
    a symmetrized graph must converge to exactly these labels.
    """
    parent = np.arange(g.n, dtype=np.int64)

    def find(x: int) -> int:
        root = x
        while parent[root] != root:
            root = parent[root]
        while parent[x] != root:           # path compression
            parent[x], x = root, int(parent[x])
        return root

    src, dst, _ = g.edges()
    for u, v in zip(src, dst):
        ru, rv = find(int(u)), find(int(v))
        if ru != rv:
            parent[max(ru, rv)] = min(ru, rv)
    return np.array([find(int(v)) for v in range(g.n)], dtype=np.int64)


def label_prop(g: CSRGraph) -> Tuple[np.ndarray, int]:
    """Synchronous min-label propagation to fixpoint (labels, rounds).

    The sequential twin of the visit-algebra ``cc`` kind: every vertex
    starts labeled with its own id and repeatedly takes the min over its
    in-labels; on symmetrized graphs the fixpoint equals union-find.
    """
    labels = np.arange(g.n, dtype=np.int64)
    src, dst, _ = g.edges()
    rounds = 0
    while True:
        nxt = labels.copy()
        np.minimum.at(nxt, dst, labels[src])
        rounds += 1
        if (nxt == labels).all():
            return labels, rounds
        labels = nxt


def kreach_stride(n: int, weights_max: float) -> float:
    """The hop-packing stride S shared by every ``kreach`` backend and the
    oracle: the smallest power of two exceeding twice the largest possible
    path weight, so ``packed = hops * S + dist`` decodes exactly in f32
    (``hops * S`` is representable and ``dist < S / 2`` can never carry)."""
    hi = 2.0 * max(1.0, float(n)) * max(1.0, float(weights_max))
    s = 2.0
    while s <= hi:
        s *= 2.0
    return s


def decode_kreach(packed: np.ndarray, stride: float, k: int):
    """Unpack the lexicographic (hops, dist) plane: ``values`` is the dist
    of the hop-minimal path where ``hops <= k`` (else +inf), ``hops`` the
    hop count (+inf unreachable).  Shared by the engine finalize, the
    distributed/baseline decodes, and the oracle — the decode is part of
    the kind's contract, so it lives in exactly one place."""
    p64 = np.asarray(packed, np.float64)
    finite = np.isfinite(p64)
    hops = np.floor(np.where(finite, p64, 0.0) / float(stride))
    dist = p64 - hops * float(stride)
    values = np.where(finite & (hops <= k), dist, np.inf).astype(np.float32)
    hops = np.where(finite, hops, np.inf).astype(np.float32)
    return values, hops


def kreach(g: CSRGraph, src: int, k: int,
           stride: float | None = None) -> Tuple[np.ndarray, np.ndarray, int]:
    """Sequential weighted k-reach: Dijkstra over the hop-shifted weights
    ``w' = f32(w + S)`` with f32 accumulation — expression-identical to the
    relaxations the block backends run, so parity is bitwise, not approximate.
    Returns (values, hops, edges) per :func:`decode_kreach`."""
    if stride is None:
        stride = kreach_stride(g.n, float(g.weights.max()) if g.m else 1.0)
    s32 = np.float32(stride)
    dist = np.full(g.n, np.inf, dtype=np.float32)
    dist[src] = np.float32(0.0)
    done = np.zeros(g.n, dtype=bool)
    heap = [(np.float32(0.0), src)]
    edges = 0
    while heap:
        d, u = heapq.heappop(heap)
        if done[u]:
            continue
        done[u] = True
        for e in range(g.indptr[u], g.indptr[u + 1]):
            v = int(g.indices[e])
            edges += 1
            nd = np.float32(d + np.float32(np.float32(g.weights[e]) + s32))
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(heap, (nd, v))
    values, hops = decode_kreach(dist, stride, k)
    return values, hops, edges


def random_walk(bg, src: int, length: int, seed: int = 0) -> np.ndarray:
    """Sequential replay of one walker's tape over the block layout.

    The randomness contract of the ``rw`` kind: at (source ``src``, step
    ``t``) the walker draws ``u = uniform(fold_in(fold_in(key(seed), src),
    t))`` and takes the ``min(floor(u * deg), deg - 1)``-th finite entry of
    its block-layout adjacency row (diagonal columns first, then the
    ``nbr_blk`` slots in order).  The trajectory is a pure function of
    (graph, seed, source, length) — independent of lane placement,
    chunking, or backend — so every runtime must reproduce it bitwise.
    Returns the visited positions (start included, <= length + 1 entries —
    a walk parked on a sink ends there, matching the runtimes' occupancy
    planes which count each visited position exactly once).
    """
    import jax

    base = jax.random.fold_in(jax.random.PRNGKey(seed), int(src))
    B = bg.block_size
    pos = int(src)
    out = [pos]
    for t in range(length):
        p, l = pos // B, pos % B
        row = np.concatenate(
            [bg.blocks[bg.diag_blk[p]][l]]
            + [np.where(bg.nbr_part[p, j] >= 0,
                        bg.blocks[bg.nbr_blk[p, j]][l], np.inf)
               for j in range(bg.nbr_part.shape[1])])
        finite = np.isfinite(row)
        deg = int(finite.sum())
        if deg == 0:
            break
        u = np.float32(jax.random.uniform(jax.random.fold_in(base, t)))
        # f32 product, exactly as the device stepper computes it
        idx = min(int(np.floor(u * np.float32(deg))), deg - 1)
        col = int(np.flatnonzero(finite)[idx])
        slot, local = col // B, col % B
        dest_part = p if slot == 0 else int(bg.nbr_part[p, slot - 1])
        pos = dest_part * B + local
        out.append(pos)
    return np.asarray(out, dtype=np.int64)


def dfs_order(g: CSRGraph, src: int) -> np.ndarray:
    """Preorder DFS labels (-1 unreachable). Host-only reference."""
    label = np.full(g.n, -1, dtype=np.int32)
    stack = [src]
    nxt = 0
    while stack:
        u = stack.pop()
        if label[u] >= 0:
            continue
        label[u] = nxt
        nxt += 1
        for e in range(g.indptr[u + 1] - 1, g.indptr[u] - 1, -1):
            v = int(g.indices[e])
            if label[v] < 0:
                stack.append(v)
    return label


def batch(fn, g: CSRGraph, sources) -> Dict[int, tuple]:
    return {int(s): fn(g, int(s)) for s in sources}
