"""The benchmark's graph data, made from the seed.

A road configuration names the ``grid2d`` generator: a ``side x side``
4-neighbour lattice, symmetrised, with edge weights uniform in
``[1, max(2, ln n))`` (the weights the DIMACS-style generators of this
repository use).  The arrays are made here, from the seed, so that the
program and the plain reference read the same data and neither makes it
for the other.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class EdgeList:
    """A directed, symmetrised edge list without duplicates or self loops."""
    n: int
    src: np.ndarray      # int64 [m]
    dst: np.ndarray      # int64 [m]
    weight: np.ndarray   # float32 [m]

    @property
    def m(self) -> int:
        return int(self.src.size)

    def out_degree(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n).astype(np.int64)


def grid2d(side: int, rng: np.random.Generator) -> EdgeList:
    """The road lattice: right and down neighbours, both directions, one
    weight per undirected edge."""
    n = side * side
    ids = np.arange(n, dtype=np.int64).reshape(side, side)
    a = np.concatenate([ids[:, :-1].ravel(), ids[:-1, :].ravel()])
    b = np.concatenate([ids[:, 1:].ravel(), ids[1:, :].ravel()])
    hi = max(2.0, float(np.log(max(n, 3))))
    w = rng.uniform(1.0, hi, size=a.size).astype(np.float32)
    return EdgeList(n=n, src=np.concatenate([a, b]),
                    dst=np.concatenate([b, a]),
                    weight=np.concatenate([w, w]))


def make_graph(config: dict, rng: np.random.Generator) -> EdgeList:
    """The configuration's graph: its ``generator`` at its ``grid_side``."""
    if config["generator"] != "grid2d":
        raise ValueError(f"unknown graph generator {config['generator']!r}")
    return grid2d(int(config["grid_side"]), rng)


def program_graph(edges: EdgeList):
    """The same edges as the program's host CSR (its ingestion entry)."""
    from repro.core.graph import CSRGraph
    return CSRGraph.from_edges(edges.n, edges.src, edges.dst, edges.weight)
