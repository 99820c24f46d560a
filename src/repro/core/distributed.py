"""Distributed FPP runtime — the buffered execution model at pod scale.

Hierarchy (DESIGN.md §2): the paper's LLC<-DRAM boundary appears twice on a
TPU pod — VMEM<-HBM inside a chip (handled by the Pallas kernels / BlockSpecs)
and HBM<-"the pod" across chips.  This module applies the SAME buffered
execution model at the second level:

  * graph partitions are sharded over the ``model`` mesh axis — each device's
    HBM permanently holds its partitions (the "cache-resident" set),
  * queries are sharded over the ``data`` (and ``pod``) axes — FPP queries are
    independent, so query shards never communicate (inter-query parallelism
    with zero synchronization, the paper's t=1 advantage without its cache
    penalty),
  * one superstep = every device visits its locally best-priority partition
    (a BSP relaxation of the paper's global priority order; Lemma A.2's
    yielding bound still applies per visit) and boundary operations are
    exchanged in batches with a single ``all_to_all`` — Algorithm 2 line 16
    *is* the collective.

The superstep body is the generic skeleton from ``core/visit.py``; this module
only supplies the mesh program around it.  Both the minplus family (SSSP/BFS)
and the push family (PPR) run through the same program — residual
contributions exchange by ``+`` through the same ``all_to_all`` routing that
minplus uses for ``min``, and the run converges when no device holds a
pending op (max-residual ratio below eps for push), a ``pmax`` across the
``model`` + query axes.

The superstep loop is a single ``lax.while_loop`` inside ``shard_map`` so the
whole FPP run lowers to one XLA program — this is what the multi-pod dry-run
compiles.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import visit as _visit
from repro.core.graph import BlockGraph
from repro.core.visit import EDGE_SHIFT, VisitAlgebra
from repro.core.yielding import YieldConfig

_shard_map = functools.partial(jax.shard_map, check_vma=False)


@dataclasses.dataclass
class ShardedGraph:
    """BlockGraph re-laid-out for P-way partition sharding.

    Every per-device slab owns ``pl = P/ndev`` consecutive partitions and the
    dense blocks whose *source* partition it owns (it needs them to relax and
    emit); destinations may be remote.
    """
    blocks: np.ndarray     # [ndev, pl, 1+dmax, B, B]; slot 0 = diagonal
    dst_part: np.ndarray   # [ndev, pl, 1+dmax] global dst partition (-1 pad)
    row_nnz: np.ndarray    # [ndev, pl, 1+dmax, B]
    deg: np.ndarray        # [ndev, pl, B]
    edge_budget: np.ndarray  # [ndev, pl]
    ndev: int
    pl: int
    dmax: int
    block_size: int
    num_parts: int

    @staticmethod
    def build(bg: BlockGraph, ndev: int, yc: YieldConfig,
              num_queries: int) -> "ShardedGraph":
        B = bg.block_size
        P_ = bg.num_parts
        pl = -(-P_ // ndev)
        p_pad = pl * ndev
        dmax = bg.nbr_blk.shape[1]
        blocks = np.full((ndev, pl, 1 + dmax, B, B), np.inf, dtype=np.float32)
        dst_part = np.full((ndev, pl, 1 + dmax), -1, dtype=np.int32)
        row_nnz = np.zeros((ndev, pl, 1 + dmax, B), dtype=np.int32)
        deg = np.zeros((ndev, pl, B), dtype=np.int32)
        part_edges = np.zeros(p_pad, dtype=np.int64)
        np.add.at(part_edges, bg.blk_src, bg.row_nnz.sum(axis=1))
        for p in range(P_):
            d, l = divmod(p, pl)
            kd = bg.diag_blk[p]
            blocks[d, l, 0] = bg.blocks[kd]
            dst_part[d, l, 0] = p
            row_nnz[d, l, 0] = bg.row_nnz[kd]
            deg[d, l] = bg.deg[p]
            for s in range(dmax):
                k = bg.nbr_blk[p, s]
                if k >= 0:
                    blocks[d, l, 1 + s] = bg.blocks[k]
                    dst_part[d, l, 1 + s] = bg.nbr_part[p, s]
                    row_nnz[d, l, 1 + s] = bg.row_nnz[k]
        budget = yc.edge_budget(part_edges, num_queries).reshape(ndev, pl)
        return ShardedGraph(blocks, dst_part, row_nnz, deg, budget,
                            ndev, pl, dmax, B, P_)


@dataclasses.dataclass
class DistributedResult:
    values: np.ndarray          # [Q, n]
    supersteps: int
    edges_processed: np.ndarray  # [Q] float64, exact
    residual: Optional[np.ndarray] = None   # [Q, n] (push kinds)


# ---------------------------------------------------------------------------
# the one mesh program: while(superstep) under shard_map


def _make_program(algebra: VisitAlgebra, mesh: Mesh, *, pl: int, dmax: int,
                  ndev: int, max_rounds: int, max_supersteps: int,
                  query_axes: Tuple[str, ...], part_axis: str):
    """jit(shard_map(while(superstep))) for one algebra on one mesh.

    Takes/returns value planes stacked as one ``[nplanes, P_pad, Q, B]``
    array so the same in/out specs serve both modes.  Edge counts ride as an
    (hi, lo) int32 pair per query — exact integer accumulation without x64.
    """
    nplanes = algebra.num_planes

    def stepper(blocks, dstp, nnz, deg, budget, vals, buf, ehi, elo):
        planes0 = tuple(vals[i] for i in range(nplanes))

        def cond(c):
            *_, done, steps = c
            return jnp.logical_and(~done, steps < max_supersteps)

        def body(c):
            planes, buf, ehi, elo, done, steps = c
            planes, buf, eq = _visit.superstep(
                blocks, dstp, nnz, deg, budget, planes, buf,
                algebra=algebra, max_rounds=max_rounds, pl=pl, dmax=dmax,
                ndev=ndev, model_axis=part_axis)
            elo = elo + eq
            spill = elo >> EDGE_SHIFT
            ehi = ehi + spill
            elo = elo - (spill << EDGE_SHIFT)
            local_pending = jnp.any(algebra.pending(buf, planes, deg))
            any_pending = local_pending
            for ax in (part_axis,) + tuple(query_axes):
                any_pending = jax.lax.pmax(any_pending.astype(jnp.int32),
                                           ax).astype(bool)
            return planes, buf, ehi, elo, ~any_pending, steps + 1

        planes, buf, ehi, elo, _, steps = jax.lax.while_loop(
            cond, body, (planes0, buf, ehi, elo, jnp.bool_(False),
                         jnp.int32(0)))
        # each device only counted edges of partitions it owns; a query's
        # total is the sum over the partition axis (replicated on return)
        ehi = jax.lax.psum(ehi, part_axis)
        elo = jax.lax.psum(elo, part_axis)
        return jnp.stack(planes), buf, ehi, elo, steps

    in_specs = _program_specs(query_axes, part_axis)
    vals_spec, state_spec, q_spec = in_specs[5:8]
    return jax.jit(_shard_map(
        stepper, mesh=mesh, in_specs=in_specs,
        out_specs=(vals_spec, state_spec, q_spec, q_spec, P()),
    ))


def _program_specs(query_axes: Tuple[str, ...], part_axis: str):
    """In-specs of the mesh program: five graph slabs split over the
    partition axis, value planes / buffer split over partitions and
    queries, the (hi, lo) edge counters split over queries."""
    state_spec = P(*((part_axis,) + tuple(query_axes) + (None,)))
    vals_spec = P(*((None, part_axis) + tuple(query_axes) + (None,)))
    q_spec = P(*query_axes)
    return (P(part_axis),) * 5 + (vals_spec, state_spec, q_spec, q_spec)


def _check_query_sharding(Q: int, mesh: Mesh, query_axes) -> int:
    nq_dev = int(np.prod([mesh.shape[a] for a in query_axes]))
    if Q % nq_dev != 0:
        raise ValueError(
            f"query batch of Q={Q} cannot shard evenly over query axes "
            f"{tuple(query_axes)} (total size {nq_dev}); pad the sources to "
            f"a multiple of {nq_dev} or re-mesh so the query-axes size "
            f"divides Q")
    return nq_dev


def _run_program(algebra: VisitAlgebra, bg: BlockGraph, sources: np.ndarray,
                 mesh: Mesh, yc: YieldConfig, max_rounds: int,
                 max_supersteps: int, query_axes, part_axis: str,
                 num_queries: Optional[int] = None,
                 init_ops: Optional[np.ndarray] = None):
    """Shared driver: build shards, init state, run, unshift edge counters."""
    ndev = int(mesh.shape[part_axis])
    Q = int(num_queries if num_queries is not None else len(sources))
    _check_query_sharding(Q, mesh, query_axes)
    sg = ShardedGraph.build(bg, ndev, yc, Q)
    B, pl, dmax = sg.block_size, sg.pl, sg.dmax
    p_pad = ndev * pl
    if init_ops is not None:
        io = np.full((p_pad, B), algebra.identity, dtype=np.float32)
        io[:bg.num_parts] = init_ops
        init_ops = io
    planes0, buf0 = _visit.init_dense_state(
        algebra, p_pad, Q, B, np.asarray(sources), trash_row=False,
        init_ops=init_ops)
    fn = _make_program(algebra, mesh, pl=pl, dmax=dmax, ndev=ndev,
                       max_rounds=max_rounds, max_supersteps=max_supersteps,
                       query_axes=tuple(query_axes), part_axis=part_axis)
    host = (sg.blocks.reshape(p_pad, 1 + dmax, B, B),
            sg.dst_part.reshape(p_pad, 1 + dmax),
            sg.row_nnz.reshape(p_pad, 1 + dmax, B),
            sg.deg.reshape(p_pad, B),
            sg.edge_budget.reshape(p_pad),
            np.stack(planes0), buf0,
            np.zeros((Q,), dtype=np.int32), np.zeros((Q,), dtype=np.int32))
    # each device receives only its own slab: placed explicitly on the
    # mesh, never staged whole on the first device
    args = [jax.device_put(x, NamedSharding(mesh, spec)) for x, spec in
            zip(host, _program_specs(tuple(query_axes), part_axis))]
    del host, sg
    vals, buf, ehi, elo, steps = fn(*args)
    del args
    edges = (np.asarray(ehi, dtype=np.float64) * float(1 << EDGE_SHIFT)
             + np.asarray(elo, dtype=np.float64))
    return np.asarray(vals), np.asarray(buf), edges, int(np.asarray(steps))


def _to_values(plane: np.ndarray, num_parts: int, Q: int, n: int):
    return plane[:num_parts].transpose(1, 0, 2).reshape(Q, -1)[:, :n]


def run_distributed_sssp(bg: BlockGraph, sources: np.ndarray, mesh: Mesh,
                         yield_config: Optional[YieldConfig] = None,
                         max_supersteps: int = 100_000,
                         query_axes=("data",), part_axis: str = "model"):
    """Batched SSSP on a (…, data, model) mesh. Returns DistributedResult.

    sources: [Q] in the reordered id space; Q must divide the query-axes size.
    """
    yc = yield_config or YieldConfig()
    algebra = _visit.minplus_algebra(yc.window())
    vals, _, edges, steps = _run_program(
        algebra, bg, sources, mesh, yc,
        max_rounds=yc.max_rounds or bg.block_size,
        max_supersteps=max_supersteps, query_axes=query_axes,
        part_axis=part_axis)
    Q = len(sources)
    return DistributedResult(_to_values(vals[0], bg.num_parts, Q, bg.n),
                             steps, edges)


def run_distributed_cc(bg: BlockGraph, num_queries: int, mesh: Mesh,
                       yield_config: Optional[YieldConfig] = None,
                       max_supersteps: int = 100_000,
                       query_axes=("data",), part_axis: str = "model"):
    """Connected components at pod scale: the minplus superstep program over
    a zero-weight block graph, seeded with every vertex's own label
    (``visit.cc_label_plane``) instead of one-hot sources.  All query lanes
    converge to the same label plane (cc is per-graph); ``num_queries``
    only sets the lane count so the result contract matches other kinds.
    """
    yc = yield_config or YieldConfig()
    # strict pending: over zero weights an equal re-sent label would keep
    # the superstep loop pending forever (see visit.minplus_algebra)
    algebra = _visit.minplus_algebra(yc.window(), strict=True)
    vals, _, edges, steps = _run_program(
        algebra, bg, np.empty(0, dtype=np.int64), mesh, yc,
        max_rounds=yc.max_rounds or bg.block_size,
        max_supersteps=max_supersteps, query_axes=query_axes,
        part_axis=part_axis, num_queries=num_queries,
        init_ops=_visit.cc_label_plane(bg))
    return DistributedResult(
        _to_values(vals[0], bg.num_parts, num_queries, bg.n), steps, edges)


def run_distributed_ppr(bg: BlockGraph, sources: np.ndarray, mesh: Mesh,
                        alpha: float = 0.15, eps: float = 1e-4,
                        yield_config: Optional[YieldConfig] = None,
                        max_supersteps: int = 100_000,
                        query_axes=("data",), part_axis: str = "model"):
    """Batched PPR: the push instantiation of the same superstep program.

    Residual contributions exchange by ``+`` through the same ``all_to_all``
    routing minplus uses; the run converges when every device's max residual
    ratio drops below eps (``pmax`` across the ``model`` + query axes).
    Returns DistributedResult with ``values`` = PPR mass and ``residual`` =
    terminal residual (pending buffered contributions folded in, so
    values + residual conserves probability mass exactly).
    """
    yc = yield_config or YieldConfig()
    algebra = _visit.push_algebra(alpha, eps)
    vals, buf, edges, steps = _run_program(
        algebra, bg, sources, mesh, yc,
        max_rounds=yc.max_rounds or 64,
        max_supersteps=max_supersteps, query_axes=query_axes,
        part_axis=part_axis)
    Q = len(sources)
    pvals = _to_values(vals[0], bg.num_parts, Q, bg.n)
    # un-consolidated buffered contributions are residual mass (engine twin)
    rvals = _to_values(vals[1] + buf, bg.num_parts, Q, bg.n)
    return DistributedResult(pvals, steps, edges, residual=rvals)


def make_walk_mesh_program(mesh: Mesh, block_size: int, length: int,
                           seed: int, walk_axes: Tuple[str, ...]):
    """jit(shard_map(fori(step))) for the rw kind: walkers shard over
    ``walk_axes``, the graph is replicated, and there is NO collective —
    walks are independent, so the pod runtime for rw is pure data
    parallelism over the same per-(source, step) tape every other rw
    runtime replays (core/randomwalk.py).
    """
    from repro.core.randomwalk import stepper_from_arrays

    def body(blocks, diag_blk, nbr_blk, nbr_part,
             pos, steps, part, src, thash, occ):
        step = stepper_from_arrays(blocks, diag_blk, nbr_blk, nbr_part,
                                   block_size, length,
                                   jax.random.PRNGKey(seed))

        def one(_, c):
            pos, steps, part, thash, occ = c
            return step(pos, steps, part, src, thash, occ, steps < length)

        pos, steps, part, thash, occ = jax.lax.fori_loop(
            0, length, one, (pos, steps, part, thash, occ))
        return pos, steps, part, thash, occ

    rep = P()
    wspec = P(tuple(walk_axes))
    occ_spec = P(tuple(walk_axes), None)
    return jax.jit(_shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, rep, rep,
                  wspec, wspec, wspec, wspec, wspec, occ_spec),
        out_specs=(wspec, wspec, wspec, wspec, occ_spec)))


def run_distributed_walks(bg: BlockGraph, sources: np.ndarray, mesh: Mesh,
                          length: int, seed: int = 0, walk_axes=None):
    """Batched random walks sharded over every mesh axis (graph replicated).

    Walker count is padded up to the axes' size with clones of walker 0
    (same tape id => same trajectory, sliced off on return).  Returns a
    ``core.randomwalk.WalkResult`` bitwise identical to the single-device
    engine loop and the synchronous baseline.
    """
    from repro.core.engine import DeviceGraph
    from repro.core.randomwalk import WalkResult, init_walk_state
    from repro.core.yielding import NO_YIELD
    walk_axes = tuple(walk_axes or mesh.axis_names)
    nshard = int(np.prod([mesh.shape[a] for a in walk_axes]))
    srcs = np.asarray(sources)
    Q = srcs.size
    Qp = -(-max(Q, 1) // nshard) * nshard
    padded = np.concatenate([srcs, np.full(Qp - Q, srcs[0] if Q else 0,
                                           dtype=srcs.dtype)])
    dg = DeviceGraph.build(bg, NO_YIELD, Qp)
    fn = make_walk_mesh_program(mesh, bg.block_size, length, seed, walk_axes)
    pos, steps, part, src, thash, occ = init_walk_state(dg, padded)
    pos, steps, part, thash, occ = fn(dg.blocks, dg.diag_blk, dg.nbr_blk,
                                      dg.nbr_part, pos, steps, part, src,
                                      thash, occ)
    return WalkResult(np.asarray(pos)[:Q], np.asarray(steps)[:Q],
                      np.asarray(thash)[:Q], visits=length,
                      occupancy=np.asarray(occ)[:Q, :bg.n])


def make_distributed_program(bg: BlockGraph, num_queries: int, mesh: Mesh, *,
                             kind: str = "sssp", alpha: float = 0.15,
                             eps: float = 1e-4,
                             yield_config: Optional[YieldConfig] = None,
                             query_axes=("data",), part_axis: str = "model",
                             max_supersteps: int = 1000,
                             length: int = 32, seed: int = 0):
    """The jitted mesh program plus matching abstract arguments.

    Public AOT handle: ``(fn, args)`` where ``args`` are
    ``ShapeDtypeStruct``s, so callers can ``fn.lower(*args)`` without
    building real shards — the multi-pod dry-run compiles it, and the
    fppcheck jaxpr/HLO passes (DESIGN.md §7) trace and budget exactly the
    program ``run_distributed_*`` executes.  ``kind``: "sssp"/"bfs"/"cc"/
    "kreach" use the minplus algebra (cc over zero weights + label init,
    kreach over hop-shifted weights — same program, different operands),
    "ppr" the push algebra, "rw" the collective-free sharded walk program
    (``length``/``seed`` are its tape parameters).
    """
    yc = yield_config or YieldConfig()
    B = bg.block_size
    if kind == "rw":
        fn = make_walk_mesh_program(mesh, B, length, seed,
                                    walk_axes=tuple(mesh.axis_names))
        P_, dmax = bg.num_parts, bg.nbr_blk.shape[1]
        f32, i32 = jnp.float32, jnp.int32
        args = (
            jax.ShapeDtypeStruct(bg.blocks.shape, f32),
            jax.ShapeDtypeStruct((P_,), i32),
            jax.ShapeDtypeStruct((P_, dmax), i32),
            jax.ShapeDtypeStruct((P_, dmax), i32),
            jax.ShapeDtypeStruct((num_queries,), i32),
            jax.ShapeDtypeStruct((num_queries,), i32),
            jax.ShapeDtypeStruct((num_queries,), i32),
            jax.ShapeDtypeStruct((num_queries,), i32),
            jax.ShapeDtypeStruct((num_queries,), jnp.uint32),
            jax.ShapeDtypeStruct((num_queries, P_ * B), f32),
        )
        return fn, args
    if kind == "ppr":
        algebra = _visit.push_algebra(alpha, eps)
        max_rounds = yc.max_rounds or 64
    elif kind in ("sssp", "bfs", "cc", "kreach"):
        algebra = _visit.minplus_algebra(yc.window(), strict=(kind == "cc"))
        max_rounds = yc.max_rounds or bg.block_size
    else:
        raise ValueError(
            f"unknown kind {kind!r}; one of sssp/bfs/ppr/cc/kreach/rw")
    ndev = int(mesh.shape[part_axis])
    pl = -(-bg.num_parts // ndev)
    p_pad = pl * ndev
    dmax = bg.nbr_blk.shape[1]
    Q = num_queries
    fn = _make_program(algebra, mesh, pl=pl, dmax=dmax, ndev=ndev,
                       max_rounds=max_rounds,
                       max_supersteps=max_supersteps,
                       query_axes=tuple(query_axes), part_axis=part_axis)
    f32, i32 = jnp.float32, jnp.int32
    args = (
        jax.ShapeDtypeStruct((p_pad, 1 + dmax, B, B), f32),
        jax.ShapeDtypeStruct((p_pad, 1 + dmax), i32),
        jax.ShapeDtypeStruct((p_pad, 1 + dmax, B), i32),
        jax.ShapeDtypeStruct((p_pad, B), i32),
        jax.ShapeDtypeStruct((p_pad,), f32),
        jax.ShapeDtypeStruct((algebra.num_planes, p_pad, Q, B), f32),
        jax.ShapeDtypeStruct((p_pad, Q, B), f32),
        jax.ShapeDtypeStruct((Q,), i32),
        jax.ShapeDtypeStruct((Q,), i32),
    )
    return fn, args


def lower_distributed_sssp(bg: BlockGraph, num_queries: int, mesh: Mesh,
                           yield_config: Optional[YieldConfig] = None,
                           query_axes=("data",), part_axis: str = "model",
                           max_supersteps: int = 1000):
    """AOT lowering entry used by the multi-pod dry-run (no real data)."""
    fn, args = make_distributed_program(
        bg, num_queries, mesh, kind="sssp", yield_config=yield_config,
        query_axes=query_axes, part_axis=part_axis,
        max_supersteps=max_supersteps)
    return fn.lower(*args)
