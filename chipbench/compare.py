"""What decides ``correct``: answers of the timed path against the plain
reference (``reference.py``), each number beside its limit.

An answer is one query's result as the program delivered it, in original
vertex ids: ``Answer(kind, source, values)``.  The numbers:

* ``sssp_rel_gap``: the widest relative gap between a delivered distance
  and the float64 reference, over every vertex the reference reaches.
* ``sssp_mismatch``: vertices whose reachability disagrees with the
  reference, or a source whose own distance is not 0.  Exact: limit 0.
* ``ppr_gap``: the widest ``|ppr(v) - p(v)| / (eps * deg(v))`` over every
  vertex, where ``ppr`` is the exact PageRank.  The configuration's own
  guarantee (every residual below ``eps * deg``, so on a symmetric graph
  ``0 <= ppr(v) - p(v) < eps * deg(v)``) states its limit: 1.
* ``ppr_mass_gap``: the push invariant ``p + ppr(r) = ppr(source)``, for
  the delivered mass ``p`` and residual ``r``: the widest L1 distance,
  in probability mass, between its two sides.  It holds to rounding
  whatever order the pushes ran in, so it reads the precision the push
  was computed in.
* ``missing``: answers that never came, or came with another status than
  ok.  Limit 0.

A run is correct when every number is at or below its limit.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from chipbench import reference
from chipbench.graphs import EdgeList


@dataclasses.dataclass
class Answer:
    kind: str
    source: int
    values: Optional[np.ndarray]     # [n] as delivered; None if never came
    path: str = "batch"              # batch | lane | coalesced | cached
    residual: Optional[np.ndarray] = None   # [n] ppr's residual mass


def sssp_numbers(edges: EdgeList, answers: List[Answer],
                 precision: str = "float64") -> Dict[str, float]:
    """The two sssp numbers for ``answers`` against ``reference.sssp``."""
    if not answers:
        return {}
    srcs = np.array([a.source for a in answers])
    uniq, inv = np.unique(srcs, return_inverse=True)
    ref = reference.sssp(edges, uniq, precision="float64")
    rel_gap, mismatch = 0.0, 0
    for a, row in zip(answers, inv):
        want = ref[row]
        got = np.asarray(a.values, dtype=np.float64)
        reach = np.isfinite(want)
        mismatch += int(np.count_nonzero(np.isfinite(got) != reach))
        mismatch += int(got[a.source] != 0.0)
        pos = reach & (want > 0) & np.isfinite(got)
        if pos.any():
            rel_gap = max(rel_gap, float(np.max(
                np.abs(got[pos] - want[pos]) / want[pos])))
    return {"sssp_rel_gap": rel_gap, "sssp_mismatch": float(mismatch)}


def ppr_numbers(edges: EdgeList, answers: List[Answer], alpha: float,
                eps: float) -> Dict[str, float]:
    """``ppr_gap`` and ``ppr_mass_gap`` for ``answers`` against the exact
    ``reference.ppr``."""
    if not answers:
        return {}
    srcs = np.array([a.source for a in answers])
    uniq, inv = np.unique(srcs, return_inverse=True)
    ref, _ = reference.ppr(edges, uniq, alpha)
    owed, _ = reference.ppr_from(
        edges, np.stack([a.residual for a in answers]), alpha)
    scale = eps * np.maximum(edges.out_degree(), 1).astype(np.float64)
    gap = mass = 0.0
    for a, row, o in zip(answers, inv, owed):
        got = np.asarray(a.values, dtype=np.float64)
        gap = max(gap, float(np.max(np.abs(ref[row] - got) / scale)))
        mass = max(mass, float(np.abs(ref[row] - o - got).sum()))
    return {"ppr_gap": gap, "ppr_mass_gap": mass}


def numbers(edges: EdgeList, answers: List[Answer], *, alpha: float,
            eps: float) -> Dict[str, float]:
    """Every number compared for a sample of answers of any kinds."""
    came = [a for a in answers if a.values is not None]
    out: Dict[str, float] = {"missing": float(len(answers) - len(came))}
    out.update(sssp_numbers(edges, [a for a in came if a.kind == "sssp"]))
    out.update(ppr_numbers(edges, [a for a in came if a.kind == "ppr"],
                           alpha, eps))
    return out


def verdict(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every reading is within its limit; a number without a
    limit, or a limit without a reading, is a fault of the cell file."""
    unknown = sorted(set(readings) - set(limits) - {"missing"})
    if unknown:
        raise KeyError(f"no limit for {unknown}")
    lim = dict(limits, missing=0.0)
    return all(readings[k] <= lim[k] for k in readings)
