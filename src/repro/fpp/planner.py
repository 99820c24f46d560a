"""Partition-size planning: the paper's "partition fits in LLC" rule as code.

The deciding performance knob of the whole design is how much graph becomes
resident per visit (paper §7.3 / Fig. 16; GPOP and CSR-segmenting reach the
same conclusion: partition-size-to-cache fit decides everything).  On TPU the
LLC is VMEM, so the planner solves

    argmax B  s.t.  working_set(B, Q) <= vmem_bytes

against an explicit :class:`MemoryModel`, and can optionally *measure* the
candidates on a query sample (``tune=True``) — the sweep previously buried in
``benchmarks/fig16_partition_size.py`` / ``benchmarks/table4_tuning.py``, now
reusable (those benchmarks call :func:`measure_run` today).

DESIGN.md §3 documents how the plan feeds the session front door.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import numpy as np

from repro.core.graph import CSRGraph
from repro.core.yielding import YieldConfig, default_delta

#: block-size candidates, smallest to largest (TPU lane-friendly powers of 2)
CANDIDATE_BLOCK_SIZES = (64, 128, 256, 512, 1024, 2048, 4096)

#: neighbor-slot budget assumed when sizing a *fused* plan before the graph
#: is partitioned (dmax is a property of the partitioning, not the plan);
#: real dmax beyond this only grows the parking scratch linearly, so the
#: budget is a planning guard, not a correctness bound
FUSED_DMAX_BUDGET = 8

#: committed dispatch yardsticks (visits/s) from BENCH_engine.json's
#: ``bench_dispatch`` section — the measured trajectory a perf PR commits.
#: Keyed (kind, dispatch, K).  ``auto_fused`` reads these to pick the visit
#: body per kind instead of a blanket ``fused=`` flag: the fused Pallas
#: visit wins for the minplus family (sssp K=64: 6809 vs 6185 visits/s)
#: but *loses* for push (ppr K=64: 2500 vs 3540 — the in-kernel push
#: round's lane-mask traffic outweighs the residency win on small
#: partitions; the regression is recorded in BENCH_engine.json's
#: ``bench_notes`` and stands until a fused-push PR beats the yardstick).
DISPATCH_YARDSTICKS = {
    ("sssp", "megastep", 8): 4597.4,
    ("sssp", "megastep", 64): 6185.4,
    ("sssp", "fused", 8): 5407.5,
    ("sssp", "fused", 64): 6809.3,
    ("ppr", "megastep", 8): 3088.4,
    ("ppr", "megastep", 64): 3539.8,
    ("ppr", "fused", 8): 2535.4,
    ("ppr", "fused", 64): 2500.3,
}

#: bfs runs the same minplus megastep/fused kernels as sssp (unit weights
#: only change the block values), so it shares sssp's yardstick row; cc and
#: kreach are minplus instantiations over transformed weights (zero /
#: hop-shifted), so they share it too.  rw has no yardstick row yet — its
#: walker loop never dispatches a megastep, so auto_fused's conservative
#: False is exactly right.
_YARDSTICK_KIND = {"bfs": "sssp", "cc": "sssp", "kreach": "sssp"}


def auto_fused(kind: str, k_visits: int = 64,
               dmax: Optional[int] = None) -> bool:
    """Pick the visit body for ``kind`` from the committed yardsticks.

    True iff the fused Pallas visit measured faster than the XLA megastep
    at the nearest committed chunk size.  Unknown kinds (no committed rows
    either way) conservatively stay on the XLA megastep — a new kind must
    land a ``bench_dispatch`` row before auto-select will fuse it.

    ``dmax`` (the partitioning's neighbor-slot count, ``bg.nbr_part
    .shape[1]``) guards the auto-select against block graphs denser than
    the :data:`FUSED_DMAX_BUDGET` the yardsticks were measured under: the
    fused kernel's pre-gathered ``[P, 1+dmax, B+1, B]`` adjacency and its
    ``(1+dmax,)`` grid both grow linearly in dmax, so past the budget the
    residency win inverts and auto-select stays on the XLA megastep.  An
    *explicit* ``fused=True`` is never overridden — callers who measured
    their own graph keep their choice.
    """
    if dmax is not None and int(dmax) > FUSED_DMAX_BUDGET:
        return False
    yk = _YARDSTICK_KIND.get(kind, kind)
    ks = sorted({k for (kk, _, k) in DISPATCH_YARDSTICKS if kk == yk})
    if not ks:
        return False
    k = min(ks, key=lambda c: abs(c - int(k_visits)))
    fused = DISPATCH_YARDSTICKS.get((yk, "fused", k))
    plain = DISPATCH_YARDSTICKS.get((yk, "megastep", k))
    return fused is not None and plain is not None and fused > plain


def pow2_bucket(demand: int, min_capacity: int = 1,
                max_capacity: int = 1024) -> int:
    """Snap a lane-count demand to its power-of-two bucket.

    Every capacity the serving layer ever instantiates comes through here
    (initial pool size, autoscale hints), so the set of compiled megastep
    shapes stays logarithmic in demand and a resize lands on a warm
    executable in the serving compile cache (keyed by this bucket) instead
    of a retrace (DESIGN.md §4.2).
    """
    demand = max(int(demand), int(min_capacity), 1)
    cap = 1
    while cap < demand:
        cap *= 2
    return max(int(min_capacity), min(int(max_capacity), cap))


@dataclasses.dataclass(frozen=True)
class MemoryModel:
    """Device memory budget the plan must fit (two-level hierarchy, §2).

    Working set of one partition visit (what must be VMEM-resident):
      adjacency block   B*B*dtype   (x2 when double-buffering the next block)
      dist/value tile   Q*B*dtype
      buffer tile       Q*B*dtype
    HBM holds the full block-sparse graph plus the [P, Q, B] state planes;
    ``hbm_bytes`` caps both so Q and B cannot silently overflow a chip.

    The defaults are the explicit model of the CPU path (Pallas kernels
    interpreted); :meth:`for_device` gives a TPU its measured row.
    """
    vmem_bytes: int = 96 * 1024 * 1024
    hbm_bytes: int = 16 * 1024 ** 3
    dtype_bytes: int = 4
    double_buffer: bool = True

    @classmethod
    def for_device(cls, device=None) -> "MemoryModel":
        """The model of ``device`` (default: JAX's first device).

        A TPU gets its ``kernels.tpu.TPU_MEMORY`` row — the VMEM limit
        every kernel requests and the chip's HBM — and a TPU kind missing
        from that table raises instead of guessing.  Any other backend
        keeps the explicit defaults.
        """
        import jax

        from repro.kernels.tpu import tpu_memory
        device = device if device is not None else jax.devices()[0]
        if device.platform != "tpu":
            return cls()
        row = tpu_memory(device.device_kind)
        return cls(vmem_bytes=row.vmem_limit_bytes, hbm_bytes=row.hbm_bytes)

    def working_set(self, block_size: int, num_queries: int) -> int:
        mult = 2 if self.double_buffer else 1
        return (mult * block_size * block_size * self.dtype_bytes
                + 2 * num_queries * block_size * self.dtype_bytes)

    def fused_working_set(self, block_size: int, num_queries: int,
                          num_planes: int, dmax: int) -> int:
        """VMEM bytes one *fused* visit holds resident (DESIGN.md §2.4).

        The fused kernel keeps every state channel (``num_planes`` value
        planes + the buffer) for the visited partition in VMEM across the
        whole visit, the in and the aliased out block each double-buffered
        by the pipeline, plus the partition's adjacency row DMA'd into
        scratch (diagonal + ``dmax`` boundary blocks of [B, B], and their
        per-row edge counts, one 8-sublane tile each), the relax source
        tile, and the emission parking scratch (two [Q, B] planes and a
        degree row per slot, slot 0 being the resident row).  This is
        deliberately larger than ``working_set``: residency across rounds
        is the fusion's point, so the planner must budget the whole visit,
        not one relaxation.
        """
        b, q, d = block_size, num_queries, self.dtype_bytes
        chans = num_planes + 1
        slots = 1 + dmax
        state = 2 * 2 * chans * q * b * d        # in + out, double-buffered
        adj = slots * (b + 8) * b * d            # blocks + nnz row tiles
        scratch = slots * (2 * q + 8) * b * d    # cand/plane/deg parking
        return state + adj + scratch + q * b * d + 2 * b * d

    def state_bytes(self, n_vertices: int, num_queries: int,
                    block_size: int) -> int:
        """HBM-resident state planes (dist + buf + one spare), padded."""
        n_pad = -(-n_vertices // block_size) * block_size
        return 3 * n_pad * num_queries * self.dtype_bytes

    def covers(self, footprint_bytes: int, block_size: int,
               num_queries: int) -> bool:
        """True if a kernel's *static* VMEM footprint is within budget.

        The fppcheck Pallas contract pass (DESIGN.md §7) computes each
        wired kernel's per-grid-step footprint from its BlockSpecs and
        asks this model — the same one that planned the block size —
        whether that footprint stays inside the working set budgeted for
        one ``(block_size, num_queries)`` partition visit.  A kernel
        whose tiles outgrow the model would thrash exactly the cache the
        planner sized for.
        """
        return (footprint_bytes <= self.working_set(block_size, num_queries)
                and footprint_bytes <= self.vmem_bytes)

    def fused_covers(self, footprint_bytes: int, block_size: int,
                     num_queries: int, num_planes: int, dmax: int) -> bool:
        """``covers`` for fused-visit kernels (``fused_model=True``
        contracts): the footprint is judged against the whole-visit
        residency budget instead of the single-relaxation working set."""
        return (footprint_bytes <= self.fused_working_set(
                    block_size, num_queries, num_planes, dmax)
                and footprint_bytes <= self.vmem_bytes)

    def fits(self, block_size: int, num_queries: int,
             n_vertices: Optional[int] = None, *,
             fused: bool = False, num_planes: int = 2,
             dmax: int = FUSED_DMAX_BUDGET,
             adjacency_bytes: int = 0) -> bool:
        """True if one visit fits VMEM and the device arrays fit HBM.

        ``adjacency_bytes`` is the dense block store the plan stages next
        to the state planes (:func:`model_block_size` estimates it from the
        degree sequence); HBM must hold both."""
        if self.working_set(block_size, num_queries) > self.vmem_bytes:
            return False
        if fused and self.fused_working_set(
                block_size, num_queries, num_planes, dmax) > self.vmem_bytes:
            return False
        if n_vertices is not None and self.state_bytes(
                n_vertices, num_queries, block_size) + int(
                    adjacency_bytes) > self.hbm_bytes:
            return False
        return True


@dataclasses.dataclass(frozen=True)
class Plan:
    """A resolved execution plan for one fork-processing pattern."""
    block_size: int
    method: str                 # partition/reorder method (partition.py)
    schedule: str               # inter-partition policy (scheduler.py)
    backend: str                # "engine" | "distributed" | "baselines"
    num_queries: int
    mem: MemoryModel
    yield_config: Optional[YieldConfig] = None   # None => per-kind default
    tuned: bool = False
    tuning_rows: tuple = ()
    #: visit-body dispatch: False = XLA megastep, True = fused Pallas
    #: kernel, "auto" = per-kind from the committed yardsticks
    #: (:func:`auto_fused`) at execution time
    fused: object = False

    def resolve_fused(self, kind: str, k_visits: int = 64,
                      dmax: Optional[int] = None) -> bool:
        """The concrete visit body for one kind under this plan."""
        if self.fused == "auto":
            return auto_fused(kind, k_visits, dmax=dmax)
        return bool(self.fused)

    def working_set_bytes(self) -> int:
        if self.fused:
            return self.mem.fused_working_set(
                self.block_size, self.num_queries, num_planes=2,
                dmax=FUSED_DMAX_BUDGET)
        return self.mem.working_set(self.block_size, self.num_queries)


def default_method(g: CSRGraph) -> str:
    """Paper §7.1: METIS-like clustering for road/web graphs, random for
    power-law social graphs (where clustering quality collapses)."""
    deg = g.out_degree()
    mean = max(1.0, float(deg.mean()))
    if float(deg.max()) > 64.0 * mean:      # heavy-tailed hub structure
        return "random"
    return "bfs"


def est_dmax(g: CSRGraph, block_size: int) -> int:
    """Pessimistic neighbor-slot estimate for one partition of size B.

    Skewed (real SNAP-style) graphs concentrate edges on a few hubs; if
    the ``B`` heaviest vertices land in one partition, their combined
    out-edges reach at best ``ceil(sum(top-B degrees) / B)`` distinct
    partitions — the floor on that partition's boundary-block count.
    Clamped to ``P - 1`` (a partition cannot neighbor more partitions than
    exist).  This is a planning estimate from the degree sequence alone,
    usable before any partitioning has run.
    """
    if g.n == 0:
        return 0
    deg = np.sort(g.out_degree())[::-1]
    top = float(deg[: int(block_size)].sum())
    num_parts = -(-g.n // int(block_size))
    return int(min(max(num_parts - 1, 0),
                   np.ceil(top / max(float(block_size), 1.0))))


def adjacency_estimate(g: CSRGraph, block_size: int,
                       mem: MemoryModel) -> int:
    """HBM bytes of the dense block store at ``block_size``, estimated
    before partitioning: about one neighborhood (the diagonal block plus
    :func:`est_dmax` boundary blocks) per partition.  Once a graph is
    partitioned its ``BlockGraph.nbytes()`` is the exact figure."""
    b = int(block_size)
    nparts = -(-g.n // b)
    return nparts * (1 + est_dmax(g, b)) * b * b * mem.dtype_bytes


def model_block_size(g: CSRGraph, num_queries: int, mem: MemoryModel,
                     candidates: Sequence[int] = CANDIDATE_BLOCK_SIZES,
                     min_parts: int = 8, fused: bool = False,
                     degree_aware: bool = True) -> int:
    """Largest candidate whose visit working set fits the memory model.

    Also keeps at least ``min_parts`` partitions alive (clamped to what the
    graph can support): with too few partitions there is nothing for the
    scheduler to choose between and buffered consolidation degenerates —
    the "smaller multiplies scheduling overhead, larger thrashes" U-shape
    of Fig. 16 has a scheduling wall on the right, not just a cache wall.

    ``degree_aware=True`` adds the skew guard for real ingested graphs:
    each candidate must also keep one visit's *neighborhood* — the diagonal
    block plus :func:`est_dmax` boundary blocks streamed against it —
    inside the VMEM budget.  On uniform-degree graphs the estimate is tiny
    and the guard never binds; on hub-heavy graphs it pushes the plan to a
    smaller B so heavy vertices split across more, smaller boundary blocks
    instead of dragging a mega-neighborhood through the cache every visit.
    """
    best = None
    for b in candidates:
        nparts = -(-g.n // b)
        if nparts < max(2, min(min_parts, g.n // candidates[0])):
            break
        hood = (1 + est_dmax(g, b)) * b * b * mem.dtype_bytes
        if degree_aware and hood > mem.vmem_bytes:
            continue   # hub neighborhoods outgrow VMEM at this B
        if mem.fits(b, num_queries, g.n, fused=fused,
                    adjacency_bytes=adjacency_estimate(g, b, mem)):
            best = b
    if best is None:
        raise ValueError(
            f"no candidate block size fits the memory model for "
            f"Q={num_queries} (smallest candidate {candidates[0]} needs "
            f"{mem.working_set(candidates[0], num_queries)} B of "
            f"{mem.vmem_bytes} B VMEM); shrink the query batch or raise "
            f"the budget")
    return best


def measure_run(session, kind: str, sources: np.ndarray,
                **overrides) -> dict:
    """Run one configuration through the session and report the sweep row.

    The reusable measurement unit behind ``autotune_block_size`` and the
    benchmark sweeps (table4 policies/thresholds, fig16 block sizes).
    Partitioning is warmed outside the timed window — it is a one-time
    per-graph cost, not part of the execution being compared.  The engine
    backend runs its K-visit megastep loop here like everywhere else, so
    the measured candidates see the real O(visits/K) dispatch cost
    (``host_syncs`` is recorded per row; benchmarks/bench_dispatch.py
    sweeps K itself).
    """
    from repro.core.queries import WEIGHT_VARIANTS
    session.prepared(block_size=overrides.get("block_size"),
                     method=overrides.get("method"),
                     weights=WEIGHT_VARIANTS.get(kind, "natural"))
    t0 = time.perf_counter()
    res = session.run(kind, sources, **overrides)
    secs = time.perf_counter() - t0
    return {
        "runtime_s": secs,
        "visits": res.stats.get("visits", 0),
        "host_syncs": res.stats.get("host_syncs", 0),
        "traffic_bytes": res.stats.get("modeled_bytes", 0.0),
        "edges_per_q": float(np.mean(res.edges_processed)),
    }


def autotune_block_size(session, kind: str, sources: np.ndarray,
                        mem: MemoryModel,
                        candidates: Sequence[int] = CANDIDATE_BLOCK_SIZES,
                        objective: str = "traffic_bytes",
                        num_queries: Optional[int] = None):
    """Measure each memory-feasible candidate; return (best_B, rows).

    Objective defaults to modeled HBM->VMEM traffic — deterministic across
    machines, and the paper's Fig. 16 shows it tracks the runtime U-shape
    (visits x bytes-per-visit).  Ties break toward measured runtime.

    Feasibility is judged at ``num_queries`` (the plan's real batch width),
    while measurement runs on the (smaller) ``sources`` sample.
    """
    g = session.graph
    nq = num_queries if num_queries is not None else len(sources)
    feasible = [b for b in candidates
                if b < max(2, g.n) and mem.fits(
                    b, nq, g.n,
                    adjacency_bytes=adjacency_estimate(g, b, mem))]
    if not feasible:
        raise ValueError(
            f"no candidate block size fits the memory model for Q={nq}; "
            f"shrink the query batch or raise the budget")
    rows = []
    for b in feasible:
        row = measure_run(session, kind, sources, block_size=b)
        row["block_size"] = b
        rows.append(row)
    best = min(rows, key=lambda r: (r[objective], r["runtime_s"]))
    return int(best["block_size"]), rows


#: default serving result-cache budget, in units of one single-lane HBM
#: plane set (``MemoryModel.state_bytes`` at Q=1).  One cached entry costs
#: roughly a third of a plane set (values [n] f32; ppr adds a residual
#: plane), so 16 plane sets hold on the order of 25-50 hot answers — wide
#: enough to cover a Zipf head, small next to the executor state itself.
RESULT_CACHE_PLANE_SETS = 16


def result_cache_budget(mem: MemoryModel, n_vertices: int, block_size: int,
                        plane_sets: int = RESULT_CACHE_PLANE_SETS) -> int:
    """Byte budget for the serving result cache (DESIGN.md §4.2).

    Priced by the same §3.1 memory model that sizes everything else: a
    small multiple (:data:`RESULT_CACHE_PLANE_SETS`) of one query lane's
    padded HBM plane set for this graph.  ``GraphServer`` takes the max
    over its registered graphs, so the cache scales with the largest
    graph being served rather than a hardcoded byte count; an explicit
    ``GraphServer(cache_bytes=...)`` replaces this default entirely.
    """
    return int(plane_sets) * mem.state_bytes(int(n_vertices), 1,
                                             int(block_size))


def autoscale_capacity(queue_depth: int, active: int, *,
                       mem: MemoryModel, n_vertices: int, block_size: int,
                       min_capacity: int = 1,
                       max_capacity: int = 1024,
                       adjacency_bytes: int = 0) -> int:
    """Suggest a lane-pool ``capacity`` from observed queue pressure.

    The serving autoscaling hint (DESIGN.md §4.2): demand is what is
    in flight plus what is waiting; the suggestion is the next power of two
    covering it (powers of two keep the set of jitted engine shapes
    logarithmic in demand), clamped to ``[min_capacity, max_capacity]`` and
    then shrunk until the §3.1 memory model accepts the visit working set
    and the HBM state planes next to the graph's ``adjacency_bytes`` of
    staged blocks at the pool's block size.  Pure function of
    its inputs — GraphServer calls it between chunks and applies a changed
    suggestion only when the pool is idle, so resizing never moves an
    in-flight lane.
    """
    cap = pow2_bucket(int(queue_depth) + int(active),
                      min_capacity=min_capacity, max_capacity=max_capacity)
    while cap > min_capacity and not mem.fits(
            block_size, cap, n_vertices, adjacency_bytes=adjacency_bytes):
        cap //= 2
    return int(cap)


def make_plan(g: CSRGraph, num_queries: int, *,
              mem: Optional[MemoryModel] = None,
              block_size: Optional[int] = None,
              method: Optional[str] = None,
              schedule: str = "priority",
              backend: str = "engine",
              yield_config: Optional[YieldConfig] = None,
              fused: object = False,
              degree_aware: bool = True) -> Plan:
    """Resolve a plan without measuring (the model-only path).

    ``FPPSession.plan(tune=True)`` upgrades the block size by measurement.
    ``fused="auto"`` defers the visit-body choice to the per-kind
    yardsticks (:func:`auto_fused`).  Block sizing always budgets the
    fused working set, the conservative bound: whatever the plan's
    default, a run may pick the fused body (``run(fused=True)``,
    ``GraphServer``'s per-kind auto), and it must compile at the planned
    B.  ``degree_aware=False`` disables the hub-skew VMEM guard in
    :func:`model_block_size` (ignored when ``block_size`` is explicit).
    ``mem`` defaults to :meth:`MemoryModel.for_device`.
    """
    mem = mem or MemoryModel.for_device()
    if fused not in (True, False, "auto"):
        raise ValueError(f"fused must be True, False, or 'auto', "
                         f"got {fused!r}")
    if block_size is None:
        block_size = model_block_size(g, num_queries, mem, fused=True,
                                      degree_aware=degree_aware)
    method = method or default_method(g)
    return Plan(block_size=int(block_size), method=method, schedule=schedule,
                backend=backend, num_queries=int(num_queries), mem=mem,
                yield_config=yield_config,
                fused=(fused if fused == "auto" else bool(fused)))


def default_yield_config(kind: str, bg) -> YieldConfig:
    """Per-query-kind yield defaults (paper Table 4 settings)."""
    if kind == "bfs":
        return YieldConfig(delta=1.0)          # Δ=1 == level-synchronous
    if kind == "ppr":
        return YieldConfig(mu_factor=100.0)    # paper's NCP setting
    if kind in ("cc", "kreach", "rw"):
        # these kinds run transformed weights (zero / hop-shifted) or no
        # weights at all, so a Δ-window derived from the block values would
        # be the wrong scale (0 for cc, the hop stride for kreach) — run
        # the full-window fixpoint instead
        return YieldConfig()
    return YieldConfig(delta=default_delta(bg.weight_max))
