"""The buffered execution engine — Algorithm 2 of the paper, TPU-native.

One *visit* makes a partition resident (HBM->VMEM via the Pallas kernels on
real hardware; a [B, B] block on CPU) and drains its buffered operations for
all Q queries at once.  The visit body itself lives in ``core/visit.py`` as a
single generic skeleton; this module owns the engine around it (device graph
staging, traffic modeling) and instantiates the skeleton for both modes.
The hot loop is *device-resident*: ``FPPEngine.run`` dispatches K-visit
megasteps (``core/visit.make_megastep``) whose scheduler decision is an
on-device argmin over the ``[P]`` metadata planes, so the host is consulted
once per K visits — O(visits/K) synchronizations instead of O(visits)
(``host_loop=True`` keeps the legacy per-visit loop as the tested oracle).
The two modes:

  minplus mode (SSSP / BFS / BC / LL):
    d <- min(d, buf)                      # apply + consolidate buffered ops
    repeat (until converged / yield):
      active = pending & Δ-window & edge-budget
      d <- min(d, minplus(d|active, W_pp))  # vectorized local relaxation
    emit: buf[j] <- min(buf[j], minplus(d|emitted, W_pj)) for each neighbor j

  push mode (PPR / NCP):
    r <- r + buf                          # residual contributions consolidate by +
    repeat: p += a*r|active; spread = ((1-a)*r/deg)|active @ Adj
    emit: buf[j] += push_acc @ Adj_pj

Everything a CPU thread did with a priority queue is done here by masking:
the Δ-window mask *is* the per-query priority order (only best-value ops
relax), and the dense min/sum buffer *is* query-centric consolidation
(DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import visit as _visit
from repro.core.graph import BlockGraph
from repro.core.oracles import decode_kreach
from repro.core.scheduler import PartitionScheduler
from repro.core.support import SupportRows
from repro.core.visit import (VisitAlgebra, VisitState, minplus_algebra,
                              push_algebra)
from repro.core.yielding import YieldConfig
from repro.kernels.minplus import ops as minplus_ops

#: ``cc`` and ``kreach`` are minplus-algebra instantiations over transformed
#: weight planes (zero weights + per-vertex label ops; hop-shifted weights),
#: so they inherit the megastep / fused-kernel / superstep machinery intact —
#: only the state init and the host-side finalize differ (DESIGN.md §2.1).
MODES = ("minplus", "push", "cc", "kreach")


class VisitStats(NamedTuple):
    visits: int
    rounds: int
    modeled_bytes: float  # modeled HBM->VMEM traffic (cache-miss analogue)
    host_syncs: int = 0   # device->host round trips the run paid (megastep:
    #                       one per K-visit chunk; host loop: one per visit)
    visit_counts: Optional[np.ndarray] = None  # [P] int64 visits/partition
    megastep_traces: int = 0  # times this run traced its megastep
    chunk_reads: int = 0      # device->host reads at chunk (visit) bounds
    relax_width: int = 0      # candidates per relaxed output: W (pull-ELL
    #                           view) or B (dense tile)
    lane_visits: int = 0      # query lanes that processed an edge, summed
    #                           over the visits (<= visits * num_queries)


# ---------------------------------------------------------------------------
# device-side graph bundle


#: BlockGraph fields staged onto the device
_STAGED = ("blocks", "row_nnz", "nbr_blk", "nbr_part", "diag_blk", "deg",
           "vmask", "ell_src", "ell_w")
_stage_lock = threading.Lock()


def _staged(bg: BlockGraph) -> dict:
    """Device copies of ``bg``'s arrays, made once per BlockGraph.

    Every engine over one partitioning — the session's runs, each serving
    pool's executor, the compile cache's warm builds — shares these
    buffers, so a graph occupies HBM once however many engines it backs.
    """
    with _stage_lock:
        arrays = bg.__dict__.get("_device_arrays")
        if arrays is None:
            arrays = {f: jnp.asarray(getattr(bg, f)) for f in _STAGED}
            bg.__dict__["_device_arrays"] = arrays
        return arrays


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=list(_STAGED) + ["edge_budget"],
                   meta_fields=["num_parts", "block_size", "dmax",
                                "ell_width"])
@dataclasses.dataclass
class DeviceGraph:
    """BlockGraph arrays staged onto device once (the in-memory graph).

    A pytree: jitted programs take it as an argument, so its arrays are
    operands of the compiled program and never constants baked into it."""
    blocks: jax.Array     # [nblk, B, B] f32, +inf absent
    row_nnz: jax.Array    # [nblk, B] i32
    nbr_blk: jax.Array    # [P, Dmax] i32 (-1 pad)
    nbr_part: jax.Array   # [P, Dmax] i32 (-1 pad)
    diag_blk: jax.Array   # [P] i32
    deg: jax.Array        # [P, B] i32
    vmask: jax.Array      # [P, B] bool
    ell_src: jax.Array    # [nblk, B, W] i32 pull-ELL source rows (W may be 0)
    ell_w: jax.Array      # [nblk, B, W] f32 their weights, +inf padding
    edge_budget: jax.Array  # [P] f32 per-query edge budget per visit
    num_parts: int
    block_size: int
    dmax: int
    ell_width: int        # W; 0 when the graph has no pull-ELL view

    @staticmethod
    def build(bg: BlockGraph, yc: YieldConfig, num_queries: int) -> "DeviceGraph":
        part_edges = np.zeros(bg.num_parts, dtype=np.int64)
        np.add.at(part_edges, bg.blk_src, bg.row_nnz.sum(axis=1))
        return DeviceGraph(
            **_staged(bg),
            edge_budget=jnp.asarray(yc.edge_budget(part_edges, num_queries)),
            num_parts=bg.num_parts,
            block_size=bg.block_size,
            dmax=bg.nbr_blk.shape[1],
            ell_width=bg.ell_width,
        )


# ---------------------------------------------------------------------------
# mode instantiations of the shared skeleton (core/visit.py)


def make_minplus_visit(dg: DeviceGraph, window: float, max_rounds: int,
                       relax: Callable = None) -> Callable:
    """SSSP/BFS visit = the generic kernel under the minplus algebra."""
    return _visit.make_visit(dg, minplus_algebra(window, relax=relax),
                             max_rounds)


def make_push_visit(dg: DeviceGraph, alpha: float, eps: float, max_rounds: int,
                    spread: Callable = None) -> Callable:
    """PPR visit = the generic kernel under the push algebra."""
    return _visit.make_visit(dg, push_algebra(alpha, eps, spread=spread),
                             max_rounds)


def init_minplus_state(dg: DeviceGraph, sources: np.ndarray) -> VisitState:
    """sources: [Q] vertex ids in the *reordered* id space."""
    return _visit.init_engine_state(minplus_algebra(np.inf), dg, sources)


def init_push_state(dg: DeviceGraph, sources: np.ndarray,
                    eps: float, alpha: float = 0.15) -> VisitState:
    return _visit.init_engine_state(push_algebra(alpha, eps), dg, sources)


#: the touched vertices one lane of a support delivery carries; a batch with
#: a wider lane falls back to reading its planes whole.  A PPR lane at
#: alpha 0.15, eps 1e-4 on a road lattice touches a few hundred.
SUPPORT_SLOTS = 4096


@functools.partial(jax.jit, static_argnums=(3, 4))
def _touched(p, r, buf, n: int, slots: int):
    """Each lane's vertices (reordered ids) whose mass or residual is
    nonzero, the residual with its buffered ops folded in as the dense
    finalize folds them: ``(count [Q], ids [Q, slots], p, r)``, the first
    ``count`` slots of each row ascending and valid."""
    P, Q, B = p.shape
    r = r + buf[:P]
    hit = ((p != 0) | (r != 0)).transpose(1, 0, 2).reshape(Q, P * B)[:, :n]
    # the k-th touched vertex is where the running count first reaches k: a
    # binary search per slot, cheaper on a v5e than jnp.nonzero's
    # scatter-add of every vertex into the slots (249 against 329 ms at
    # P 256, Q 128, B 1024)
    seen = jnp.cumsum(hit, axis=1, dtype=jnp.int32)
    ids = jnp.minimum(jax.vmap(jnp.searchsorted, (0, None))(
        seen, jnp.arange(1, slots + 1, dtype=jnp.int32)), n - 1)
    at = (ids // B, jnp.arange(Q)[:, None], ids % B)
    return seen[:, -1], ids, p[at], r[at]


# ---------------------------------------------------------------------------
# host-driven engine (Alg. 2 outer loop)


@dataclasses.dataclass
class EngineResult:
    values: np.ndarray        # [Q, n] distances (minplus) or PPR mass (push);
    #                           SupportRows under support=True
    residual: Optional[np.ndarray]
    edges_processed: np.ndarray  # [Q] float64, exact (host-accumulated)
    stats: VisitStats
    visit_order: list


class FPPEngine:
    """Single-device ForkGraph engine.

    mode: "minplus" (SSSP/BFS) or "push" (PPR).
    """

    def __init__(self, bg: BlockGraph, mode: str = "minplus",
                 yield_config: YieldConfig = YieldConfig(),
                 schedule: str = "priority", num_queries: int = 1,
                 alpha: float = 0.15, eps: float = 1e-4, seed: int = 0,
                 use_pallas: bool = False, k_visits: int = 64,
                 fused: bool = False, frontier_mode: str = "dense",
                 hop_budget: int = 8, hop_stride: float = 1.0):
        if mode not in MODES:
            raise ValueError(f"unknown engine mode {mode!r}; one of {MODES}")
        if k_visits < 1:
            raise ValueError(f"k_visits must be >= 1, got {k_visits}")
        if mode == "cc" and bg.n >= (1 << 24):
            raise ValueError(
                f"cc labels ride the f32 minplus planes, exact only below "
                f"2^24 vertices; got n={bg.n}")
        self.bg = bg
        self.mode = mode
        self.yc = yield_config
        self.num_queries = num_queries
        self.alpha, self.eps = alpha, eps
        self.hop_budget, self.hop_stride = int(hop_budget), float(hop_stride)
        self.seed = seed
        self.k_visits = int(k_visits)
        self.fused = bool(fused)
        self.frontier_mode = frontier_mode
        self.dg = DeviceGraph.build(bg, yield_config, num_queries)
        self.scheduler = PartitionScheduler(schedule, bg.num_parts, seed)
        max_rounds = yield_config.max_rounds or (
            bg.block_size if mode != "push" else 64)
        self.max_rounds = max_rounds
        # fused visits run the whole body inside one pallas_call, so the
        # algebra must keep its XLA relax/spread — a pallas_call nested in
        # a Pallas kernel body would not lower.  use_pallas keeps the
        # dense Pallas relax for every block (no pull-ELL form).
        if mode == "push":
            spread = (minplus_ops.masked_matmul_pallas
                      if use_pallas and not fused else None)
            self.algebra: VisitAlgebra = push_algebra(alpha, eps,
                                                      spread=spread)
        else:
            relax = (minplus_ops.minplus_pallas
                     if use_pallas and not fused else None)
            # cc propagates over zero weights, where an equal re-sent label
            # would pend (and re-emit) forever under the default <= rule
            self.algebra = minplus_algebra(yield_config.window(), relax=relax,
                                           strict=(mode == "cc"))
        self._visit = _visit.make_visit(self.dg, self.algebra, max_rounds)
        # the hot loop: K visits per host dispatch, scheduler on device;
        # fused=True swaps the visit body for the fused Pallas kernel
        self._megastep = _visit.make_megastep(
            self.dg, self.algebra, max_rounds, policy=schedule,
            K=self.k_visits, fused=self.fused,
            frontier_mode=self.frontier_mode)
        # the fused kernel contracts dense tiles whatever the algebra has
        self.relax_width = (bg.block_size if self.fused
                            else _visit.relax_width(self.dg, self.algebra))
        # modeled HBM traffic per visit: diagonal block + touched out-blocks +
        # two state tiles — the cache-miss analogue used by fig10.
        B = bg.block_size
        out_blocks = (bg.nbr_blk >= 0).sum(axis=1)
        self._visit_bytes = ((1 + out_blocks) * B * B * 4
                             + 2 * num_queries * B * 4).astype(np.float64)

    def init_state(self, sources: np.ndarray) -> VisitState:
        if self.mode == "cc":
            # cc is a per-graph computation: every vertex is a source and
            # every query lane converges to the same label plane, so the
            # one-hot source injection is replaced by a full init plane
            # (sources only set the lane count)
            return _visit.init_engine_state(
                self.algebra, self.dg, np.empty(0, dtype=np.int64),
                num_queries=self.num_queries,
                init_ops=_visit.cc_label_plane(self.bg))
        return _visit.init_engine_state(self.algebra, self.dg, sources)

    def run(self, sources: np.ndarray, max_visits: int | None = None,
            record_order: bool = False,
            host_loop: bool = False,
            support: bool = False) -> EngineResult:
        """Run the engine to completion (or ``max_visits``).

        The default path dispatches K-visit *megasteps*: partition selection
        happens on device and the host is consulted O(visits/K) times — one
        dispatch + one small stats harvest per chunk (``stats.host_syncs``
        counts them).  ``host_loop=True`` keeps the legacy one-sync-per-visit
        loop with the numpy :class:`PartitionScheduler`; it is the oracle the
        megastep is tested against (tests/test_megastep.py) and the baseline
        the dispatch microbench compares (benchmarks/bench_dispatch.py).

        ``support=True`` (push mode) delivers ``values`` and ``residual``
        as :class:`~repro.core.support.SupportRows`: each lane's touched
        vertices are gathered on the device and only they cross to the
        host (:meth:`_finalize`).
        """
        if len(sources) != self.num_queries:
            raise ValueError(
                f"got {len(sources)} sources for an engine planned for "
                f"num_queries={self.num_queries}; rebuild the engine (or the "
                f"session plan) with num_queries={len(sources)}")
        with spans.span(spans.INIT_STATE) as sp:
            state = self.init_state(np.asarray(sources))
            spans.note(sp, bytes=sum(
                x.nbytes for x in jax.tree_util.tree_leaves(state)))
        max_visits = max_visits or 2000 * self.bg.num_parts
        if host_loop:
            return self._run_host_loop(state, max_visits, record_order,
                                       support)
        visits = rounds = lanes = syncs = reads = traces = 0
        order: list = []
        counts = np.zeros(self.dg.num_parts, dtype=np.int64)
        # edge counts leave the device as an exact (hi, lo) int32 pair per
        # chunk and accumulate here in float64, so totals stay exact past
        # 2^24 (f32) edges.
        edges = np.zeros(self.num_queries, dtype=np.float64)
        key = jax.random.PRNGKey(self.seed)
        # each read below is one device->host transfer
        harvest_reads = 5 if record_order else 4
        while visits < max_visits:
            limit = min(self.k_visits, max_visits - visits)
            chunk = syncs
            with spans.span(spans.DISPATCH, chunk=chunk) as sp:
                # a wrapped megastep may not count its traces
                before = getattr(self._megastep, "traces", 0)
                state, ms = self._megastep(state, jnp.int32(visits),
                                           jnp.int32(limit), key)
                traced = getattr(self._megastep, "traces", 0) - before
                spans.note(sp, traces=traced)
            traces += traced
            syncs += 1
            with spans.span(spans.SYNC, chunk=chunk) as sp:
                v = int(ms.visits)      # the one host sync per chunk
                spans.note(sp, visits=v)
            reads += 1
            if v == 0:
                break
            key = ms.key
            with spans.span(spans.HARVEST, chunk=chunk, reads=harvest_reads):
                edges += _visit.harvest_edges(ms.eq_hi, ms.eq_lo)
                counts += np.asarray(ms.visit_counts, dtype=np.int64)
                tallies = np.asarray(ms.tallies)
                rounds += int(tallies[0])
                lanes += int(tallies[1])
                if record_order:
                    order.extend(int(x) for x in np.asarray(ms.order)[:v])
            reads += harvest_reads
            visits += v
            if v < limit:
                # the while-cond can only exit below the limit when no
                # partition holds a pending op: the run is complete, no
                # empty confirmation dispatch needed
                break
        stats = VisitStats(
            visits=visits, rounds=rounds,
            modeled_bytes=float(counts @ self._visit_bytes),
            host_syncs=syncs, visit_counts=counts, megastep_traces=traces,
            chunk_reads=reads, relax_width=self.relax_width,
            lane_visits=lanes)
        with spans.span(spans.FINALIZE):
            return self._finalize(state, edges, stats, order, support)

    def _run_host_loop(self, state: VisitState, max_visits: int,
                       record_order: bool,
                       support: bool = False) -> EngineResult:
        """Legacy per-visit loop: prio/stamp/ops sync to host, numpy argmin,
        one jitted visit per dispatch — O(visits) host synchronizations."""
        visits = rounds = lanes = reads = 0
        counts = np.zeros(self.dg.num_parts, dtype=np.int64)
        order: list = []
        counter = 0
        edges = np.zeros(self.num_queries, dtype=np.float64)
        while visits < max_visits:
            prio = np.asarray(state.prio)
            stamp = np.asarray(state.stamp)
            ops = np.asarray(state.ops_count)
            reads += 3
            p = self.scheduler.select(prio, stamp, ops)
            if p is None:
                break
            state, (r, eq) = self._visit(state, jnp.int32(p),
                                         jnp.int32(counter))
            eq = np.asarray(eq)
            edges += eq
            lanes += int(np.count_nonzero(eq))
            counter += 1
            visits += 1
            rounds += int(r)
            reads += 2
            counts[p] += 1
            if record_order:
                order.append(p)
        stats = VisitStats(visits=visits, rounds=rounds,
                           modeled_bytes=float(counts @ self._visit_bytes),
                           host_syncs=visits, visit_counts=counts,
                           chunk_reads=reads, relax_width=self.relax_width,
                           lane_visits=lanes)
        with spans.span(spans.FINALIZE):
            return self._finalize(state, edges, stats, order, support)

    def _finalize(self, state: VisitState, edges: np.ndarray,
                  stats: VisitStats, order: list,
                  support: bool = False) -> EngineResult:
        n = self.bg.n
        if support and self.mode == "push":
            slots = min(n, SUPPORT_SLOTS)
            count, idx, p, r = jax.device_get(_touched(
                state.planes[0], state.planes[1], state.buf, n, slots))
            if count.max(initial=0) <= slots:
                held = np.arange(slots) < count[:, None]
                rows, cols = np.nonzero(held)[0], idx[held]
                q = self.num_queries
                return EngineResult(
                    SupportRows.from_entries(rows, cols, p[held], q, n),
                    SupportRows.from_entries(rows, cols, r[held], q, n),
                    edges, stats, order)
            # a lane touched more vertices than the slots hold: read the
            # planes whole
            res = self._finalize(state, edges, stats, order)
            return dataclasses.replace(
                res, values=SupportRows.from_dense(res.values),
                residual=SupportRows.from_dense(res.residual))
        if self.mode != "push":
            dist = state.planes[0]
            vals = np.asarray(dist).transpose(1, 0, 2).reshape(
                self.num_queries, -1)[:, :n]
            if self.mode == "kreach":
                # the packed lex-(hops, dist) plane unpacks on host; the hop
                # plane rides the residual slot of the result contract
                vals, hops = decode_kreach(vals, self.hop_stride,
                                           self.hop_budget)
                return EngineResult(vals, hops, edges, stats, order)
            return EngineResult(vals, None, edges, stats, order)
        pvals = np.asarray(state.planes[0]).transpose(1, 0, 2).reshape(
            self.num_queries, -1)[:, :n]
        # pending buffered contributions ARE residual mass that was never
        # consolidated (below-eps ops at termination): fold them in so
        # p + r conserves probability exactly (test_ppr_mass_is_conserved)
        rfull = np.asarray(state.planes[1]) + np.asarray(
            state.buf[:self.bg.num_parts])
        rvals = rfull.transpose(1, 0, 2).reshape(
            self.num_queries, -1)[:, :n]
        return EngineResult(pvals, rvals, edges, stats, order)
