"""Backend dispatch: three execution paths behind one result contract.

  engine       single-device buffered FPP engine (core/engine.py, Alg. 2)
  distributed  shard_map pod runtime (core/distributed.py) — partitions over
               the "model" mesh axis, queries over "data"
  baselines    global-frontier GPS engines (core/baselines.py), kept callable
               so every speedup claim stays one flag away from its baseline

Every (backend, kind) pair in ``BACKENDS × KINDS`` dispatches — the engine
and the distributed runtime instantiate the same ``core/visit.py`` algebra
for both the minplus (sssp/bfs) and push (ppr) families, so no combination
raises.  Whatever the backend, the caller gets the same contract back:
``values`` is float32 ``[Q, n]`` in the *reordered* id space (the session
maps back to original ids), ``edges_processed`` is float64 ``[Q]`` holding
exact integral counts.  That uniformity is what lets tests assert all three
paths against core/oracles.py bit-for-bit on dtype/shape (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro import spans
from repro.core.baselines import (global_minplus, global_push,
                                  global_random_walks)
from repro.core.engine import FPPEngine
from repro.core.graph import BlockGraph
from repro.core.oracles import decode_kreach
from repro.core.support import SupportRows
from repro.core.visit import cc_label_plane
from repro.core.yielding import YieldConfig

BACKENDS = ("engine", "distributed", "baselines")
KINDS = ("sssp", "bfs", "ppr", "cc", "kreach", "rw")

#: engine mode per kind; rw bypasses the visit-algebra engine entirely
#: (core/randomwalk.py is its own buffered loop over the same substrate)
_ENGINE_MODE = {"sssp": "minplus", "bfs": "minplus", "ppr": "push",
                "cc": "cc", "kreach": "kreach"}


@dataclasses.dataclass
class BackendResult:
    values: np.ndarray                 # [Q, n] float32, reordered id space
    residual: Optional[np.ndarray]     # [Q, n] float32 (push kinds) or None
    edges_processed: np.ndarray        # [Q] float64
    stats: dict                        # visits / rounds / supersteps / bytes


def _normalize(values, residual, edges, stats) -> BackendResult:
    if isinstance(values, SupportRows):
        return BackendResult(values, residual,
                             np.asarray(edges, dtype=np.float64), stats)
    return BackendResult(
        values=np.ascontiguousarray(np.asarray(values, dtype=np.float32)),
        residual=(None if residual is None
                  else np.asarray(residual, dtype=np.float32)),
        edges_processed=np.asarray(edges, dtype=np.float64),
        stats=stats)


def default_mesh():
    """(data=1, model=ndev) mesh over whatever devices this process has."""
    import jax
    return jax.make_mesh((1, len(jax.devices())), ("data", "model"))


def canonicalize_cc(values: np.ndarray) -> np.ndarray:
    """Rewrite raw cc label rows (reordered-rep ids, any id space) into the
    canonical min-original-id-per-component labels.

    ``values``: [Q, n] rows in the ORIGINAL vertex order whose cells hold
    the backend's reordered representative ids.  Two vertices share a
    component iff they share a cell value, so grouping by value and taking
    the min row index (= min original id) yields labels independent of the
    partitioning permutation — the form union-find (oracles.connected_
    components) produces directly.
    """
    values = np.asarray(values)
    n = values.shape[1]
    out = np.empty_like(values, dtype=np.float32)
    done: dict = {}
    for q in range(values.shape[0]):
        key = values[q].tobytes()       # cc lanes are identical; decode once
        if key not in done:
            reps = values[q].astype(np.int64)
            min_orig = np.full(n, n, dtype=np.int64)
            np.minimum.at(min_orig, reps, np.arange(n))
            done[key] = min_orig[reps].astype(np.float32)
        out[q] = done[key]
    return out


def _rw_result(res, stats: dict) -> BackendResult:
    """WalkResult -> the uniform backend contract: values = occupancy
    counts [Q, n] (start + each step's position), edges = steps taken."""
    return _normalize(res.occupancy, None,
                      np.asarray(res.steps, dtype=np.float64), stats)


def run_query(backend: str, kind: str, bg: BlockGraph, sources: np.ndarray,
              *, schedule: str = "priority",
              yield_config: Optional[YieldConfig] = None,
              alpha: float = 0.15, eps: float = 1e-4,
              use_pallas: bool = False, mesh=None,
              max_visits: Optional[int] = None,
              fused: bool = False,
              frontier_mode: str = "dense",
              k: int = 8, hop_stride: float = 1.0,
              length: int = 32, seed: int = 0,
              support: bool = False) -> BackendResult:
    """Run one query batch (sources in reordered ids) on one backend.

    ``fused=True`` (engine backend only) swaps each visit body for the
    fused Pallas kernel (kernels/fused_visit): the whole visit — apply,
    relax rounds, emission, scheduler refresh — runs inside one
    pallas_call, bit-identical to the XLA megastep for the deterministic
    algebras.  ``frontier_mode="sparse"`` selects the chunk-skipping
    relaxation for late sparse frontiers (minplus kinds only).

    The transformed-weight kinds expect ``bg`` already built from the
    matching weight variant (session.prepared handles this): ``cc`` a
    zero-weight graph, ``kreach`` the hop-shifted weights with
    ``hop_stride`` = the shift S (``oracles.kreach_stride``) and ``k`` the
    hop budget.  ``rw`` takes the natural graph plus ``length``/``seed``;
    its values are occupancy counts and its trajectories are identical
    across all three backends (see core/randomwalk.py's tape contract).
    Raw ``cc`` values are reordered-rep labels — callers canonicalize with
    :func:`canonicalize_cc` after mapping back to original ids.
    ``support=True`` has the engine deliver ``ppr`` planes as
    :class:`~repro.core.support.SupportRows`; other backends ignore it.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    if kind not in KINDS:
        raise ValueError(f"unknown query kind {kind!r}; one of {KINDS}")
    if fused and backend != "engine":
        raise ValueError(
            f"fused=True is an engine-backend flag; backend={backend!r} "
            f"runs its own visit bodies")
    sources = np.asarray(sources)

    if kind == "rw":
        if backend == "engine":
            from repro.core.randomwalk import run_random_walks
            res = run_random_walks(bg, sources, length, seed=seed)
            return _rw_result(res, {"visits": res.visits})
        if backend == "baselines":
            res = global_random_walks(bg, sources, length, seed=seed)
            return _rw_result(res, {"rounds": res.visits})
        from repro.core.distributed import run_distributed_walks
        res = run_distributed_walks(bg, sources, mesh or default_mesh(),
                                    length, seed=seed)
        return _rw_result(res, {"supersteps": res.visits})

    if backend == "engine":
        with spans.span(spans.ENGINE) as sp:
            eng = FPPEngine(bg, mode=_ENGINE_MODE[kind],
                            num_queries=len(sources),
                            yield_config=yield_config or YieldConfig(),
                            schedule=schedule, alpha=alpha, eps=eps,
                            use_pallas=use_pallas, fused=fused,
                            frontier_mode=frontier_mode,
                            hop_budget=k, hop_stride=hop_stride)
            spans.note(sp, relax_width=eng.relax_width,
                       program_reused=int(eng.program_reused))
        res = eng.run(sources, max_visits=max_visits, support=support)
        return _normalize(res.values, res.residual, res.edges_processed, {
            "visits": res.stats.visits, "rounds": res.stats.rounds,
            "modeled_bytes": res.stats.modeled_bytes,
            "host_syncs": res.stats.host_syncs,
            "visit_counts": res.stats.visit_counts,
            "megastep_traces": res.stats.megastep_traces,
            "chunk_reads": res.stats.chunk_reads,
            "relax_width": res.stats.relax_width,
            "lane_visits": res.stats.lane_visits})

    if backend == "baselines":
        if kind == "ppr":
            res = global_push(bg, sources, alpha=alpha, eps=eps)
            residual = np.zeros_like(res.values)  # Jacobi push drains below eps
        elif kind == "cc":
            res = global_minplus(bg, sources,
                                 init_plane=cc_label_plane(bg))
            residual = None
        else:
            res = global_minplus(bg, sources)
            residual = None
        values = res.values
        if kind == "kreach":
            values, residual = decode_kreach(values, hop_stride, k)
        return _normalize(values, residual, res.edges_processed, {
            "rounds": res.rounds, "modeled_bytes": res.modeled_bytes,
            "modeled_bytes_shared": res.modeled_bytes_shared})

    # distributed: the same visit algebra at pod scale (DESIGN.md §2.2)
    from repro.core.distributed import (run_distributed_cc,
                                        run_distributed_ppr,
                                        run_distributed_sssp)
    mesh = mesh or default_mesh()
    if kind == "ppr":
        res = run_distributed_ppr(bg, sources, mesh, alpha=alpha, eps=eps,
                                  yield_config=yield_config)
    elif kind == "cc":
        res = run_distributed_cc(bg, len(sources), mesh,
                                 yield_config=yield_config)
    else:
        res = run_distributed_sssp(bg, sources, mesh,
                                   yield_config=yield_config)
    values, residual = res.values, res.residual
    if kind == "kreach":
        values, residual = decode_kreach(values, hop_stride, k)
    return _normalize(values, residual, res.edges_processed, {
        "supersteps": res.supersteps})
