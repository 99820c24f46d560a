"""FPPSession — the front door: plan → execute → stream (DESIGN.md §3).

One object owns the whole life of a fork-processing pattern:

    sess = FPPSession(g)                       # host CSR, original vertex ids
    sess.plan(num_queries=64)                  # memory-model block-size plan
    res = sess.run("sssp", sources)            # original ids in AND out
    res = sess.run("sssp", sources, backend="baselines")   # same contract
    res = sess.run("ppr", seeds, backend="distributed")    # pod-scale push
    bc  = sess.bc(sources)                     # applications ride the same path
    stream = sess.stream("sssp", capacity=8)   # queries arriving over time

Above the session sits the serving layer: ``serve/graph_server.py``
(DESIGN.md §4.2) registers one session per graph and multiplexes
multi-tenant request streams onto per-(graph, kind) ``stream()`` executors.

Everything downstream of here (engine, distributed runtime, baselines) speaks
the *reordered* id space and partition-major state; the session is the only
layer that owns ``perm`` and hides it.  All three backends serve every query
kind — both visit-algebra families (minplus and push, core/visit.py) run on
the single-device engine AND the shard_map pod runtime — and return identical
dtypes/shapes (see backends.py), so swapping ``backend=`` is a one-word
experiment, not a rewrite.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional, Tuple

import numpy as np

from repro import spans
from repro.core.graph import BlockGraph, CSRGraph
from repro.core.partition import partition
from repro.core.support import SupportRows
from repro.core.yielding import YieldConfig
from repro.fpp import backends as _backends
from repro.fpp import planner as _planner
from repro.fpp.planner import MemoryModel, Plan


#: the counts the ``fpp.run`` span carries at its end: (arg, stats key)
_RUN_COUNTS = (("visits", "visits"), ("chunks", "host_syncs"),
               ("chunk_reads", "chunk_reads"),
               ("megastep_traces", "megastep_traces"),
               ("lane_visits", "lane_visits"))


@dataclasses.dataclass
class SessionResult:
    """Backend-independent result, in the ORIGINAL vertex id space."""
    kind: str
    backend: str
    values: np.ndarray                # [Q, n] float32; SupportRows under
    #                                   delivery="support"
    residual: Optional[np.ndarray]    # [Q, n] float32 (ppr) or None
    edges_processed: np.ndarray       # [Q] float64
    stats: dict
    sources: np.ndarray               # [Q] original ids as submitted


class FPPSession:
    """Plan → execute → stream for fork-processing patterns on one graph."""

    def __init__(self, g: CSRGraph, *, mem: Optional[MemoryModel] = None):
        self.graph = g
        self.mem = mem or MemoryModel.for_device()
        self._plan: Optional[Plan] = None
        # (block_size, method, weight_variant) -> (BlockGraph, perm)
        self._prepared: Dict[tuple, Tuple[BlockGraph, np.ndarray]] = {}
        self._kreach_stride: Optional[float] = None
        # the serving compile cache warms megasteps on background threads
        # (serve/compile_cache.py); partitioning must not race itself
        self._prepare_lock = threading.Lock()

    @property
    def kreach_stride(self) -> float:
        """The hop-shift S for this graph's kreach packing (a per-graph
        constant: ``oracles.kreach_stride`` of n and the max weight), shared
        by the "shift" weight variant and the result decode so they can
        never disagree."""
        if self._kreach_stride is None:
            from repro.core.oracles import kreach_stride
            g = self.graph
            self._kreach_stride = kreach_stride(
                g.n, float(g.weights.max()) if g.m else 1.0)
        return self._kreach_stride

    # ------------------------------------------------------------------ plan

    def plan(self, num_queries: int = 64, *,
             block_size: Optional[int] = None,
             method: Optional[str] = None,
             schedule: str = "priority",
             backend: str = "engine",
             yield_config: Optional[YieldConfig] = None,
             fused: object = False,
             tune: bool = False,
             tune_sources: Optional[np.ndarray] = None,
             tune_kind: str = "sssp") -> "FPPSession":
        """Resolve the execution plan; chainable.

        ``tune=True`` measures every memory-feasible block size on a query
        sample (``tune_sources``, default: first min(8, Q) vertices with
        out-edges) and keeps the one with the least modeled traffic —
        feeding benchmarks/fig16's sweep back into the system.

        ``fused`` may be True/False (a blanket visit-body choice) or
        ``"auto"``: each run/stream then picks the body per kind from the
        committed dispatch yardsticks (``planner.auto_fused`` — fused
        wins for minplus kinds, the XLA megastep for ppr).
        """
        p = _planner.make_plan(self.graph, num_queries, mem=self.mem,
                               block_size=block_size, method=method,
                               schedule=schedule, backend=backend,
                               yield_config=yield_config, fused=fused)
        self._plan = p
        if tune and block_size is None:
            if tune_sources is None:
                deg = self.graph.out_degree()
                cand = np.flatnonzero(deg > 0)
                tune_sources = cand[:min(8, cand.size)]
            best, rows = _planner.autotune_block_size(
                self, tune_kind, np.asarray(tune_sources), self.mem,
                num_queries=num_queries)
            self._plan = dataclasses.replace(
                p, block_size=best, tuned=True,
                tuning_rows=tuple(tuple(sorted(r.items())) for r in rows))
        return self

    @property
    def current_plan(self) -> Plan:
        if self._plan is None:
            self.plan()
        return self._plan

    # -------------------------------------------------------------- prepare

    def prepared(self, *, block_size: Optional[int] = None,
                 method: Optional[str] = None,
                 unit_weights: bool = False,
                 weights: Optional[str] = None):
        """(BlockGraph, perm) for the plan (or overrides), cached per
        weight variant.

        ``weights`` names a ``core/queries.reweight`` variant (natural /
        unit / zero / shift); ``unit_weights=True`` is the legacy spelling
        of ``weights="unit"``.  Reweighting never touches the structure, so
        every variant of one (block_size, method) shares the same perm —
        each just carries its own block values.
        """
        from repro.core.queries import reweight
        p = self.current_plan
        bs = int(block_size or p.block_size)
        meth = method or p.method
        variant = weights or ("unit" if unit_weights else "natural")
        key = (bs, meth, variant)
        with spans.span(spans.PREPARE), self._prepare_lock:
            if key not in self._prepared:
                stride = self.kreach_stride if variant == "shift" else None
                g = reweight(self.graph, variant, stride=stride)
                self._prepared[key] = partition(g, bs, method=meth)
            return self._prepared[key]

    # ------------------------------------------------------------------ run

    def run(self, kind: str, sources: np.ndarray, *,
            backend: Optional[str] = None,
            schedule: Optional[str] = None,
            yield_config: Optional[YieldConfig] = None,
            block_size: Optional[int] = None,
            method: Optional[str] = None,
            alpha: float = 0.15, eps: float = 1e-4,
            use_pallas: bool = False, mesh=None,
            max_visits: Optional[int] = None,
            fused: Optional[bool] = None,
            frontier_mode: str = "dense",
            k: int = 8, length: int = 32,
            seed: int = 0, delivery: str = "dense") -> SessionResult:
        """Execute one query batch.  Sources and values use original ids.

        ``fused`` defaults to the plan's setting (``plan(fused=True)``);
        pass it explicitly to override per run.  ``frontier_mode="sparse"``
        selects the fused kernel's chunk-skipping late-frontier relaxation
        (minplus kinds only).

        The session resolves each kind's weight variant and decode: ``cc``
        values come back as canonical min-original-id component labels
        (identical across every lane and backend), ``kreach`` takes the
        hop budget ``k`` (values = dist of the hop-minimal path within the
        budget; residual = hop counts), ``rw`` takes ``length``/``seed``
        (values = occupancy counts; fused is not applicable and is
        ignored — the walker loop has no megastep to fuse).

        ``delivery="support"`` (``ppr`` only) returns ``values`` and
        ``residual`` as :class:`~repro.core.support.SupportRows`, each
        lane's touched vertices alone: the engine gathers them on the
        device, so a batch moves kilobytes to the host, not two dense
        ``[Q, n]`` planes.
        """
        from repro.core.queries import WEIGHT_VARIANTS
        if delivery not in ("dense", "support"):
            raise ValueError(f"unknown delivery {delivery!r}; dense or "
                             f"support")
        if delivery == "support" and kind != "ppr":
            raise ValueError(f"delivery='support' is for ppr, not {kind!r}")
        support = delivery == "support"
        sources = np.asarray(sources)
        p = self.current_plan
        bk = backend or p.backend
        with spans.span(spans.RUN, kind=kind, queries=len(sources),
                        backend=bk) as sp:
            bg, perm = self.prepared(
                block_size=block_size, method=method,
                weights=WEIGHT_VARIANTS.get(kind, "natural"))
            with spans.span(spans.ENGINE):
                # the Δ-window is a constant of the engine's programs; its
                # default scans the whole host block store
                yc = (yield_config if yield_config is not None else
                      (p.yield_config
                       or _planner.default_yield_config(kind, bg)))
            if fused is None:
                # the plan's default applies only where it can: other
                # backends run their own visit bodies (explicit fused=True
                # still raises).  plan(fused="auto") resolves per kind from
                # committed yardsticks, falling back to the XLA megastep
                # when this partitioning is denser than the fused-kernel
                # dmax budget.
                fused = bk == "engine" and kind != "rw" and p.resolve_fused(
                    kind, dmax=bg.nbr_part.shape[1])
            out = _backends.run_query(
                bk, kind, bg, perm[sources],
                schedule=schedule or p.schedule, yield_config=yc,
                alpha=alpha, eps=eps, use_pallas=use_pallas, mesh=mesh,
                max_visits=max_visits,
                fused=bool(fused) and kind != "rw",
                frontier_mode=frontier_mode, k=k,
                hop_stride=(self.kreach_stride if kind == "kreach" else 1.0),
                length=length, seed=seed, support=support)
            with spans.span(spans.FINALIZE):
                # back to original vertex ids, as row-major planes:
                # ``x[:, perm]`` would return them column-major, a strided
                # row for every later per-query scan (the NCP sweep's)
                if support:
                    values, residual = (
                        (x if isinstance(x, SupportRows)
                         else SupportRows.from_dense(x)).relabel(
                             np.argsort(perm))
                        for x in (out.values, out.residual))
                else:
                    values = np.take(out.values, perm, axis=1)
                    residual = (None if out.residual is None
                                else np.take(out.residual, perm, axis=1))
                if kind == "cc":
                    values = _backends.canonicalize_cc(values)
            spans.note(sp, **{arg: out.stats[key] for arg, key in _RUN_COUNTS
                              if key in out.stats})
        return SessionResult(kind=kind, backend=bk,
                             values=values, residual=residual,
                             edges_processed=out.edges_processed,
                             stats=out.stats, sources=sources)

    # --------------------------------------------------------------- stream

    def stream(self, kind: str = "sssp", capacity: int = 16, *,
               schedule: Optional[str] = None,
               yield_config: Optional[YieldConfig] = None,
               alpha: float = 0.15, eps: float = 1e-4,
               harvest_every: int = 1, k_visits: int = 64,
               fused: Optional[bool] = None, megastep=None,
               k: int = 8, length: int = 32, seed: int = 0):
        """A streaming executor: submit query batches as they arrive
        (fpp/streaming.py); answers match the one-shot run of the union.
        ``k_visits`` sets the device-resident chunk size — admission and
        harvest happen at chunk boundaries (DESIGN.md §3.3), so it is also
        the lane-recycling latency knob: lower K = fresher harvests, more
        host syncs.  ``harvest_every`` only affects the legacy per-visit
        ``step()`` cadence; the default ``pump()``/``run()`` path harvests
        once per chunk regardless.  ``fused`` defaults to the plan's
        (per-kind under ``fused="auto"``); ``megastep`` injects a warm
        pre-compiled executable (serve/compile_cache.py) so the executor
        never traces.

        ``kind="rw"`` returns a :class:`~repro.fpp.streaming.WalkExecutor`
        (same submit/pump/take_finished surface) whose walks are bitwise
        the tape walks of ``run("rw", ...)`` at the executor's ``length``
        and ``seed``; ``kind="kreach"`` streams at hop budget ``k``.
        """
        from repro.fpp.streaming import StreamingExecutor, WalkExecutor
        from repro.core.queries import WEIGHT_VARIANTS
        if kind == "rw":
            # ``megastep`` doubles as the warm compiled walk visit here —
            # one injection surface for every lane kind
            return WalkExecutor(self, capacity=capacity, length=length,
                                seed=seed, k_visits=k_visits, visit=megastep)
        if fused is None:
            bg, _ = self.prepared(
                weights=WEIGHT_VARIANTS.get(kind, "natural"))
            fused = self.current_plan.resolve_fused(
                kind, k_visits, dmax=bg.nbr_part.shape[1])
        return StreamingExecutor(
            self, kind=kind, capacity=capacity,
            schedule=schedule or self.current_plan.schedule,
            yield_config=yield_config, alpha=alpha, eps=eps,
            harvest_every=harvest_every, k_visits=k_visits,
            fused=bool(fused), megastep=megastep, k=k)

    # --------------------------------------------------- paper applications

    def bc(self, sources: np.ndarray, **run_kw):
        """Approximate betweenness centrality from sampled BFS roots."""
        from repro.core.applications import bc_accumulate
        res = self.run("bfs", sources, **run_kw)
        return bc_accumulate(self.graph, np.asarray(sources),
                             res.values), res

    def landmarks(self, landmarks: np.ndarray, **run_kw):
        """Landmark labeling: one SSSP per landmark, labels in original ids."""
        from repro.core.applications import LandmarkLabels
        res = self.run("sssp", landmarks, **run_kw)
        return LandmarkLabels(np.asarray(landmarks), res.values), res

    def ncp(self, seeds: np.ndarray, *, alpha: float = 0.15,
            eps: float = 1e-4, max_size: Optional[int] = None, **run_kw):
        """Network community profile from a fleet of PPRs: ``(profile,
        result)``, where ``profile`` is the min conductance per log2
        cluster-size bin over every seed's sweep cut.

        The sweep reads only each seed's support ``{v : p(v) > 0}`` and its
        CSR rows (``applications.ncp_profile``); its conductances equal the
        whole-graph sweep's (``oracles.sweep_conductance_ref``) bit for bit.
        With ``delivery="support"`` in ``run_kw`` the supports come from
        the device as they are (``run``) and no dense plane is made; else
        one scan of the batch's values finds them.  The sweep runs under an
        ``fpp.sweep`` span noted with ``seeds`` and the summed ``support``;
        ``result.stats["lane_visits"]`` counts the query lanes the push
        visits moved."""
        from repro.core.applications import ncp_profile, support_of
        res = self.run("ppr", seeds, alpha=alpha, eps=eps, **run_kw)
        with spans.span(spans.SWEEP, seeds=len(res.sources)) as sp:
            support = support_of(res.values)
            profile = ncp_profile(self.graph, res.values, max_size=max_size,
                                  support=support)
            spans.note(sp, support=int(support[0].size))
        return profile, res

    def random_walks(self, sources: np.ndarray, length: int = 32, *,
                     seed: int = 0, block_size: Optional[int] = None,
                     method: Optional[str] = None):
        """Buffered random walks (core/randomwalk.py), original ids in/out.

        Walkers are FPP queries under the same plan as everything else:
        the session hands reordered sources to ``core/queries.run_rw`` and
        maps the final ``positions`` back through the inverse permutation,
        so callers never see the partition-major id space.  ``steps`` and
        ``trajectory_hash`` are id-space-independent and pass through.
        """
        import dataclasses as _dc

        from repro.core.queries import run_rw
        sources = np.asarray(sources)
        bg, perm = self.prepared(block_size=block_size, method=method)
        res = run_rw(bg, perm[sources], length, seed=seed)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(len(perm))
        return _dc.replace(res, positions=inv[res.positions])
