"""Rows of a ``[Q, n]`` plane kept on their supports.

A push run touches a few hundred vertices of each lane on a road-scale
graph; ``FPPSession.run(..., delivery="support")`` hands its planes back
in this form instead of as dense ``[Q, n]`` arrays (DESIGN.md §3.5).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class SupportRows:
    """Row ``i``'s nonzero entries are ``data[indptr[i]:indptr[i + 1]]`` at
    the ascending columns ``indices[indptr[i]:indptr[i + 1]]``; every other
    entry of the ``[Q, n]`` plane is zero."""
    indptr: np.ndarray      # [Q + 1] int64
    indices: np.ndarray     # [nnz] int64
    data: np.ndarray        # [nnz] float32
    n: int

    @property
    def shape(self):
        return (self.indptr.size - 1, self.n)

    def rows(self) -> np.ndarray:
        """[nnz] the row of each entry."""
        return np.repeat(np.arange(self.shape[0]), np.diff(self.indptr))

    @classmethod
    def from_entries(cls, rows, cols, vals, q: int, n: int) -> "SupportRows":
        """From ``(row, col, value)`` entries, at most one per cell and in
        any order; zero values are dropped."""
        keep = np.asarray(vals) != 0
        rows = np.asarray(rows, dtype=np.int64)[keep]
        cols = np.asarray(cols, dtype=np.int64)[keep]
        vals = np.asarray(vals, dtype=np.float32)[keep]
        order = np.lexsort((cols, rows))
        indptr = np.zeros(q + 1, dtype=np.int64)
        np.cumsum(np.bincount(rows, minlength=q), out=indptr[1:])
        return cls(indptr, cols[order], vals[order], n)

    @classmethod
    def from_dense(cls, x: np.ndarray) -> "SupportRows":
        rows, cols = np.nonzero(x)
        return cls.from_entries(rows, cols, x[rows, cols], *x.shape)

    def relabel(self, new_id: np.ndarray) -> "SupportRows":
        """The same rows with column ``c`` renamed ``new_id[c]``."""
        return SupportRows.from_entries(self.rows(), new_id[self.indices],
                                        self.data, *self.shape)

    def row(self, i: int) -> np.ndarray:
        """Row ``i`` as a dense float32 ``[n]`` vector."""
        out = np.zeros(self.n, dtype=np.float32)
        at = slice(self.indptr[i], self.indptr[i + 1])
        out[self.indices[at]] = self.data[at]
        return out

    def dense(self) -> np.ndarray:
        """The whole float32 ``[Q, n]`` plane."""
        out = np.zeros(self.shape, dtype=np.float32)
        out[self.rows(), self.indices] = self.data
        return out
