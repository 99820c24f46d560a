"""Graph containers: host-side CSR and the TPU-native block-sparse BlockGraph.

The BlockGraph is the paper's "LLC-sized partition" adapted to TPU: vertices are
reordered so each partition is a contiguous range of ``block_size`` vertices, and
the adjacency is stored as dense ``[B, B]`` blocks for every partition pair that
contains at least one edge.  Dense blocks are what a VPU/MXU can actually chew on;
block-sparsity recovers the graph's sparsity at partition granularity (the same
granularity the paper's buffers operate at).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

INF = np.float32(np.inf)


@dataclasses.dataclass
class CSRGraph:
    """Host-side CSR. ``indptr[u]:indptr[u+1]`` are out-edges of ``u``."""

    indptr: np.ndarray   # int64 [n+1]
    indices: np.ndarray  # int32 [m]
    weights: np.ndarray  # float32 [m]
    n: int
    m: int

    @staticmethod
    def from_edges(n: int, src: np.ndarray, dst: np.ndarray,
                   weights: Optional[np.ndarray] = None,
                   symmetrize: bool = False,
                   dedup: bool = True) -> "CSRGraph":
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        if weights is None:
            weights = np.ones(src.shape[0], dtype=np.float32)
        weights = np.asarray(weights, dtype=np.float32)
        if symmetrize:
            src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
            weights = np.concatenate([weights, weights])
        # drop self loops
        keep = src != dst
        src, dst, weights = src[keep], dst[keep], weights[keep]
        if dedup and src.size:
            key = src * np.int64(n) + dst
            order = np.argsort(key, kind="stable")
            key, src, dst, weights = key[order], src[order], dst[order], weights[order]
            first = np.concatenate([[True], key[1:] != key[:-1]])
            # keep the minimum weight among duplicates: since sorted stable, use
            # np.minimum.reduceat over groups
            starts = np.flatnonzero(first)
            weights = np.minimum.reduceat(weights, starts) if starts.size else weights
            src, dst = src[first], dst[first]
        order = np.argsort(src, kind="stable")
        src, dst, weights = src[order], dst[order], weights[order]
        indptr = np.zeros(n + 1, dtype=np.int64)
        np.add.at(indptr, src + 1, 1)
        indptr = np.cumsum(indptr)
        return CSRGraph(indptr=indptr, indices=dst.astype(np.int32),
                        weights=weights.astype(np.float32), n=n, m=int(dst.size))

    def out_degree(self) -> np.ndarray:
        return np.diff(self.indptr).astype(np.int32)

    def permute(self, perm: np.ndarray) -> "CSRGraph":
        """Relabel vertices: new id of old vertex v is ``perm[v]``."""
        src = np.repeat(np.arange(self.n, dtype=np.int64), np.diff(self.indptr))
        return CSRGraph.from_edges(self.n, perm[src], perm[self.indices],
                                   self.weights, dedup=False)

    def edges(self):
        src = np.repeat(np.arange(self.n, dtype=np.int64), np.diff(self.indptr))
        return src, self.indices.astype(np.int64), self.weights


@dataclasses.dataclass
class BlockGraph:
    """Block-sparse dense-block adjacency over contiguous vertex partitions.

    Vertices are assumed already reordered (see partition.py) so that partition
    ``p`` owns vertices ``[p*B, (p+1)*B)`` of the padded id space.

    blocks      float32 [nblk, B, B]  blocks[k][u_loc, v_loc] = w(u, v), +inf absent
    blk_src     int32   [nblk]        source partition of block k
    blk_dst     int32   [nblk]        destination partition of block k
    nbr_blk     int32   [P, Dmax]     block ids of partition p's out-blocks (-1 pad),
                                      EXCLUDING the diagonal block
    nbr_part    int32   [P, Dmax]     destination partition per entry (-1 pad)
    diag_blk    int32   [P]           block id of (p, p); always present
    row_nnz     int32   [nblk, B]     out-degree of each local row within block k
    deg         int32   [P, B]        total out-degree of each vertex (padded: 0)
    vmask       bool    [P, B]        True for real (non padding) vertices
    ell_src     int32   [nblk, B, W]  pull-ELL view of ``blocks``: the source rows
                                      ``u`` with a finite ``blocks[k, u, v]``,
                                      listed per destination column ``v``
                                      (padding: row 0)
    ell_w       float32 [nblk, B, W]  their weights, the same f32 values as
                                      ``blocks`` (padding: +inf)

    ``W`` is the widest destination column over all blocks.  The view is
    built only when ``W <= B * ELL_MAX_WIDTH_SHARE``; otherwise (hub-heavy
    graphs, whose columns fill up) both arrays have width 0 and every
    relaxation reads the dense tiles.
    """

    blocks: np.ndarray
    blk_src: np.ndarray
    blk_dst: np.ndarray
    nbr_blk: np.ndarray
    nbr_part: np.ndarray
    diag_blk: np.ndarray
    row_nnz: np.ndarray
    deg: np.ndarray
    vmask: np.ndarray
    ell_src: np.ndarray
    ell_w: np.ndarray
    block_size: int
    num_parts: int
    n: int                 # real vertex count (pre-padding)
    m: int

    @property
    def n_padded(self) -> int:
        return self.num_parts * self.block_size

    @property
    def ell_width(self) -> int:
        """``W`` of the pull-ELL view; 0 when the view was not built."""
        return self.ell_src.shape[2]

    @staticmethod
    def from_csr(g: CSRGraph, block_size: int) -> "BlockGraph":
        B = int(block_size)
        P = max(1, -(-g.n // B))
        n_pad = P * B
        src, dst, w = g.edges()
        psrc = (src // B).astype(np.int64)
        pdst = (dst // B).astype(np.int64)
        pair = psrc * P + pdst
        # block ids for every (psrc, pdst) pair that appears, plus all diagonals
        diag_pairs = np.arange(P, dtype=np.int64) * P + np.arange(P, dtype=np.int64)
        uniq = np.unique(np.concatenate([pair, diag_pairs]))
        nblk = int(uniq.size)
        blk_src = (uniq // P).astype(np.int32)
        blk_dst = (uniq % P).astype(np.int32)
        blocks = np.full((nblk, B, B), INF, dtype=np.float32)
        bk = np.searchsorted(uniq, pair)
        ul = (src % B).astype(np.int64)
        vl = (dst % B).astype(np.int64)
        w = w.astype(np.float32)
        # duplicate edges (a CSR built with dedup=False) keep their minimum
        flat = blocks.reshape(nblk, B * B)
        np.minimum.at(flat, (bk, ul * B + vl), w)
        ell_src, ell_w = _pull_ell(nblk, B, bk, ul, vl, w)
        diag_blk = np.searchsorted(uniq, diag_pairs).astype(np.int32)
        # neighbor lists excluding the diagonal
        nbrs: list[list[int]] = [[] for _ in range(P)]
        for k in range(nblk):
            if blk_src[k] != blk_dst[k]:
                nbrs[int(blk_src[k])].append(k)
        dmax = max(1, max((len(x) for x in nbrs), default=1))
        nbr_blk = np.full((P, dmax), -1, dtype=np.int32)
        nbr_part = np.full((P, dmax), -1, dtype=np.int32)
        for p in range(P):
            for j, k in enumerate(nbrs[p]):
                nbr_blk[p, j] = k
                nbr_part[p, j] = blk_dst[k]
        row_nnz = np.isfinite(blocks).sum(axis=2).astype(np.int32)
        deg = np.zeros((P, B), dtype=np.int32)
        full_deg = np.zeros(n_pad, dtype=np.int64)
        np.add.at(full_deg, src, 1)
        deg[:, :] = full_deg.reshape(P, B)
        vmask = (np.arange(n_pad).reshape(P, B) < g.n)
        return BlockGraph(blocks=blocks, blk_src=blk_src, blk_dst=blk_dst,
                          nbr_blk=nbr_blk, nbr_part=nbr_part, diag_blk=diag_blk,
                          row_nnz=row_nnz, deg=deg, vmask=vmask,
                          ell_src=ell_src, ell_w=ell_w,
                          block_size=B, num_parts=P, n=g.n, m=g.m)

    def nbytes(self) -> int:
        return sum(a.nbytes for a in
                   (self.blocks, self.nbr_blk, self.nbr_part, self.diag_blk,
                    self.row_nnz, self.deg, self.vmask, self.ell_src,
                    self.ell_w))

    def part_of(self, v: int) -> int:
        return int(v) // self.block_size

    def local_of(self, v: int) -> int:
        return int(v) % self.block_size


#: the widest pull-ELL column, as a share of B, for which the view is built
#: and the XLA megastep relaxes over it instead of the dense tiles.  On a
#: v5e at Q = 64, B = 1024 a relaxation over the view took 4.9 + 2.04 W us
#: (13.1, 37.1 and 135.4 us at W = 4, 16, 64) and one over the dense tile
#: 92.0 us, so the view stops paying near W = 43, a share of 0.042; 1/32
#: keeps a quarter of margin (PERF.md §6).  The dense cost per output
#: grows with B and the view's with W, so the rule is a share of B.
ELL_MAX_WIDTH_SHARE = 1 / 32


def _pull_ell(nblk: int, B: int, bk, ul, vl, w):
    """``(ell_src, ell_w)`` [nblk, B, W] from the block-local edge arrays.

    Entries are sorted by (block, destination column, source row); a
    duplicate (block, u, v) keeps its minimum weight, as the dense store
    does, so the view holds exactly the finite values of ``blocks``.
    Width 0 when the widest column exceeds ``B * ELL_MAX_WIDTH_SHARE``.
    """
    key = (bk * B + vl) * B + ul
    order = np.argsort(key, kind="stable")
    key, w = key[order], w[order]
    new = np.ones(key.size, dtype=bool)
    new[1:] = key[1:] != key[:-1]
    starts = np.flatnonzero(new)
    if key.size:
        w = np.minimum.reduceat(w, starts)
    col, u = key[starts] // B, key[starts] % B    # col = block * B + v
    first = np.ones(col.size, dtype=bool)
    first[1:] = col[1:] != col[:-1]
    pos = np.arange(col.size)
    rank = pos - np.maximum.accumulate(np.where(first, pos, 0))
    width = int(rank.max(initial=0)) + 1
    if width > B * ELL_MAX_WIDTH_SHARE:
        width = 0
    ell_src = np.zeros((nblk * B, width), dtype=np.int32)
    ell_w = np.full((nblk * B, width), INF, dtype=np.float32)
    if width:
        ell_src[col, rank] = u
        ell_w[col, rank] = w
    return ell_src.reshape(nblk, B, width), ell_w.reshape(nblk, B, width)


def vmem_block_size(vmem_bytes: int = 96 * 1024 * 1024,
                    num_queries: int = 256,
                    dtype_bytes: int = 4,
                    double_buffer: bool = True) -> int:
    """Pick B so (adjacency block + state tiles) fit VMEM — the paper's
    ``partition size = LLC size`` rule mapped to the TPU memory hierarchy.

    Working set per resident partition visit:
      adjacency block  B*B*dtype  (x2 if double buffered)
      dist tile        Q*B*dtype
      buffer tile      Q*B*dtype
    """
    mult = 2 if double_buffer else 1
    best = 128
    for b in (128, 256, 512, 1024, 2048, 4096):
        ws = mult * b * b * dtype_bytes + 2 * num_queries * b * dtype_bytes
        if ws <= vmem_bytes:
            best = b
    return best
