"""The bytes a visit needs, and the chip's peaks.

A visit of partition ``p`` (``core/visit.py``'s Algorithm 2 body) needs
at the least, for a batch of Q queries:

* the partition's real out-edges once: 4 B of weight and 4 B of index
  each (``8 * nnz(p)``);
* its state read and written once: the algebra's value planes and its
  buffer row, 4 B per query and real vertex (``2 * 4 * Q * (planes + 1)
  * nv(p)``);
* the buffered ops it emits, read and written once at every distinct
  vertex outside ``p`` that one of its edges reaches (``2 * 4 * Q *
  targets(p)``).

The dense ``[B, B]`` tiles the implementation streams are not counted:
the number is the work the algorithm needs, whatever implements it.  The
least time for those bytes is ``bytes / HBM bandwidth``; no VPU peak is
published for f32 min/add, so no operations bound is claimed.
"""
from __future__ import annotations

import json
import os

import numpy as np

#: value planes per algebra (``minplus``: distances; ``push``: p and r)
PLANES = {"sssp": 1, "bfs": 1, "ppr": 2}
_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peak row of ``device_kind``; an unknown kind is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    row = table["kinds"].get(device_kind)
    if row is None:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table['kinds'])}")
    return row


def partition_bytes(bg, num_queries: int, kind: str) -> np.ndarray:
    """[P] bytes one visit of each partition of the block graph ``bg``
    needs (module docstring), for ``num_queries`` queries of ``kind``."""
    P = bg.num_parts
    nnz = np.zeros(P, dtype=np.float64)
    np.add.at(nnz, bg.blk_src, bg.row_nnz.sum(axis=1))
    targets = np.zeros(P, dtype=np.float64)
    off = np.flatnonzero(bg.blk_src != bg.blk_dst)
    for k in off:
        targets[bg.blk_src[k]] += np.count_nonzero(
            np.isfinite(bg.blocks[k]).any(axis=0))
    nv = bg.vmask.sum(axis=1).astype(np.float64)
    q4 = 4.0 * num_queries
    return (8.0 * nnz + 2.0 * q4 * (PLANES[kind] + 1) * nv
            + 2.0 * q4 * targets)


def needed_bytes(bg, visit_counts: np.ndarray, num_queries: int,
                 kind: str) -> float:
    """Bytes needed by ``visit_counts[p]`` visits of each partition."""
    return float(np.asarray(visit_counts, dtype=np.float64)
                 @ partition_bytes(bg, num_queries, kind))
