"""One Pallas kernel per partition visit: the fused Algorithm-2 body.

The XLA megastep (``core/visit._make_visit_body``) runs one visit as a
chain of separate ops — frontier consolidation, the relax ``while_loop``,
a vmapped neighbor ``contrib``, a segment-combine scatter, and the
metadata refresh — so the resident partition's ``[Q, B]`` state planes
round-trip HBM between every stage.  This kernel fuses the whole visit
into a single ``pallas_call`` (the paper's "process the partition to
completion while LLC-resident", PAPER.md §4, mapped to VMEM —
DESIGN.md §2.4):

  grid step 0        DMA the visited partition's adjacency row (diagonal
                     block + every boundary block, by scalar-prefetched
                     block id) and their per-row edge counts from HBM into
                     VMEM scratch, consolidate via ``frontier_tile``
                     (minplus) or the ``r += buf`` push begin, relax to
                     convergence / yield in an in-kernel
                     ``lax.while_loop`` built on ``minplus_relax`` /
                     ``push_tile``, then compute ALL neighbor
                     contributions and the full (visit + emission) edge
                     count in one shot — contributions park in a VMEM
                     scratch that persists across grid steps.
  grid steps 1..dmax one neighbor partition each: segment-combine the
                     parked contribution into the neighbor's buffer
                     channel and write the neighbor's whole state block
                     back (an output block is never read from HBM, so
                     every channel is copied through), parking the
                     combined row for the refresh.
  grid step dmax     additionally refreshes every touched partition's
                     scheduler metadata, one SMEM entry per grid slot.

Scalar-prefetched index vectors (``PrefetchScalarGridSpec``) steer each
grid step's state BlockSpec at the visited partition's rows, so only the
rows the visit actually touches move between HBM and VMEM.  Invalid
neighbor slots (the ``-1`` padding of ``dg.nbr_part``) are pointed at the
trash row ``P`` and skip their DMA and contribution; their grid steps
copy the trash row through unchanged, mirroring the XLA path's
``mode="drop"`` scatters.

State is *packed* for the kernel (:meth:`FusedVisit.pack`):

  * the value planes and the buffer row ride as channels of one
    ``[P+1, C, Q, B]`` array (one fetch + one write-back per step
    instead of 2C + 2 of them);
  * the scheduler metadata (priority, op count, stamp) are three
    ``[P+1]`` tables in HBM.  The kernel sees only the visit's ``1+dmax``
    touched entries: their pre-visit priority and stamp, and the visited
    partition's edge budget, come in through SMEM; the refreshed entries
    go out through SMEM and are scattered back into the tables.  SMEM use
    and per-visit scalar work are independent of the partition count;
  * the visit's round counter and per-query edge counts come back as
    their own small outputs.

The adjacency is read straight from the device graph's ``[nblk, B, B]``
blocks: nothing is gathered or copied per partition in HBM.

``make_megastep`` keeps the packed form across a whole K-visit chunk
and unpacks once per dispatch.

Bit-parity with the XLA megastep oracle is by construction, not by
accident (pinned in ``tests/test_fused_visit.py``):

  * the inner-round math is expression-identical (``frontier_tile`` /
    ``push_tile`` vs ``minplus_algebra.begin`` / ``push_algebra.step``),
    and the relax contraction is an exact ``min`` over the same f32 sums
    (chunking reassociates it losslessly) resp. the very same
    ``algebra.contrib`` callable;
  * the emission mask is recovered from the relax result —
    ``isfinite(payload)`` ≡ the minplus emit set (an emitted row's value
    is always finite), ``payload > 0`` ≡ the push ``acc > 0`` mask —
    and the emission edge count is the XLA expression verbatim;
  * each neighbor row is written by exactly one grid step
    (``BlockGraph.from_csr`` guarantees unique, diagonal-free neighbor
    lists — validated here at build time), so per-row read-modify-write
    equals the XLA segment-combine scatter, and the metadata refresh
    observes the combined rows just as the XLA gather-refresh runs after
    the full scatter;
  * edge counters accumulate in int32, and integer addition is
    order-independent.

``frontier_mode="sparse"`` (minplus only) switches the relax/emission
contractions to ``minplus_relax(skip_inactive=True)``: late-round
frontiers leave most source columns at +inf, and a chunk of +inf sources
contributes only +inf to an exact min — skipped work, identical bits.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.minplus.minplus import minplus_relax
from repro.kernels.tpu import compiler_params, on_tpu

INF = jnp.inf
#: mirrors core.visit._BIG_STAMP (kernels/ must not import core/)
_BIG_STAMP = np.iinfo(np.int32).max - 1


class PackedState(NamedTuple):
    """Kernel-side layout of ``core.visit.VisitState``.

    ``state[p, k]`` for ``k < num_planes`` is value plane k of partition
    p; channel ``num_planes`` is the buffered-ops row (row P = trash).
    ``prio``/``ops``/``stamp`` are the scheduler tables with a trash entry
    at index P.
    """
    state: jax.Array  # [P+1, C, Q, B] f32
    prio: jax.Array   # [P+1] f32
    ops: jax.Array    # [P+1] i32
    stamp: jax.Array  # [P+1] i32


class FusedGraph(NamedTuple):
    """The device-graph operands of the kernel, laid out once per chunk."""
    blocks: jax.Array    # [nblk, B, B] f32 (HBM, DMA'd per visit)
    nnz: jax.Array       # [nblk, 1, B] i32 per-row edge counts (HBM)
    nbr_part: jax.Array  # [P, dmax] i32 (-1 pad)
    nbr_blk: jax.Array   # [P, dmax] i32 (-1 pad)
    diag_blk: jax.Array  # [P] i32
    deg: jax.Array       # [P+1, 1, B] i32 (row P = trash)
    budget: jax.Array    # [P+1] f32 per-visit edge budget


class FusedVisit(NamedTuple):
    """The fused visit + the pack/unpack bridges to ``VisitState`` arrays.

    ``graph(dg) -> FusedGraph`` lays the device graph out for the kernel
    (traceable; call it once per chunk, outside the visit loop).
    ``visit(fg, packed, p, counter) -> (packed', rounds, eq)`` with ``eq``
    the exact int32 per-query edge count of this visit.
    """
    graph: Callable
    pack: Callable
    visit: Callable
    unpack: Callable


def _validate_neighbor_lists(dg) -> None:
    """The RMW emission requires each neighbor row be visited exactly once.

    ``BlockGraph.from_csr`` builds ``nbr_part`` from unique off-diagonal
    (src, dst) partition pairs, so this holds by construction; a graph
    built some other way must satisfy it too or fall back to the XLA
    megastep (whose segment-combine scatter tolerates duplicates).
    """
    nbr = np.asarray(dg.nbr_part)
    P = dg.num_parts
    if (nbr == np.arange(P)[:, None]).any():
        raise ValueError(
            "fused visit: nbr_part contains self-edges — the resident "
            "partition's row is written at grid step 0; use the XLA "
            "megastep for graphs with diagonal neighbor entries")
    s = np.sort(nbr, axis=1)
    if ((s[:, 1:] == s[:, :-1]) & (s[:, 1:] >= 0)).any():
        raise ValueError(
            "fused visit: nbr_part contains duplicate neighbor entries — "
            "the per-row read-modify-write would double-apply them; use "
            "the XLA megastep (its scatter folds duplicates)")


def make_fused_visit(dg, algebra, max_rounds: int, *,
                     frontier: Callable, push: Callable,
                     frontier_mode: str = "dense",
                     interpret: Optional[bool] = None) -> FusedVisit:
    """Build the fused visit for one device graph's shape + algebra.

    ``dg`` supplies the static shape (partitions, block size, neighbor
    slots) and the neighbor lists validated here; the arrays themselves
    reach the kernel through :meth:`FusedVisit.graph` as traced
    arguments, never as constants baked into the program.

    ``frontier`` / ``push`` are the kernel-safe tile ops
    (``kernels.frontier.ops.frontier_tile``,
    ``kernels.ppr_push.ops.push_tile``) — passed in by
    ``core/visit.make_megastep`` so the dispatch wiring lives in the
    dispatch table, not in a kernels-internal import.
    """
    name = algebra.name
    if name not in ("minplus", "push"):
        raise ValueError(f"fused visit: unknown algebra {name!r}")
    if frontier_mode not in ("dense", "sparse"):
        raise ValueError(f"unknown frontier_mode {frontier_mode!r}; "
                         f"one of ('dense', 'sparse')")
    if frontier_mode == "sparse" and name != "minplus":
        raise ValueError(
            "sparse frontier mode skips all-inf source chunks of an exact "
            "min — only the minplus algebra has that identity; push-mode "
            "PPR runs dense")
    _validate_neighbor_lists(dg)
    if interpret is None:
        interpret = not on_tpu()
    sparse = frontier_mode == "sparse"
    np_ = algebra.num_planes
    C = np_ + 1
    P = dg.num_parts
    B = dg.block_size
    dmax = dg.nbr_part.shape[1]
    sdx = 1 + dmax  # slot 0 = the resident row, 1.. = neighbors
    window = algebra.param("window") if name == "minplus" else 0.0
    strict = (bool(dict(algebra.params).get("strict", 0.0))
              if name == "minplus" else False)
    alpha = algebra.param("alpha") if name == "push" else 0.0
    eps = algebra.param("eps") if name == "push" else 0.0
    combine = algebra.combine
    contrib = algebra.contrib
    prio_of = algebra.prio_of
    identity = INF if name == "minplus" else 0.0
    #: the plane the metadata refresh reads (minplus: dist; push: r —
    #: emission leaves both unchanged, so parking them per step is exact)
    prio_plane = 0 if name == "minplus" else 1

    def relax(src_ref, w_ref):
        return minplus_relax(src_ref, w_ref, skip_inactive=sparse)

    def kernel(rowb_ref, blk_ref, cnt_ref,
               state_ref, prio_ref, stamp_ref, budget_ref,
               deg_ref, blocks_hbm, nnz_hbm,
               o_state_ref, o_prio_ref, o_ops_ref, o_stamp_ref,
               o_rounds_ref, o_eq_ref,
               w_scr, nnz_scr, src_scr, cand_scr, plane_scr, deg_scr, sem):
        i = pl.program_id(0)
        cnt = cnt_ref[0]
        deg_row = deg_ref[0]                     # [1, B]

        def dma(s):
            return (pltpu.make_async_copy(blocks_hbm.at[blk_ref[s]],
                                          w_scr.at[s], sem.at[0, s]),
                    pltpu.make_async_copy(nnz_hbm.at[blk_ref[s]],
                                          nnz_scr.at[s], sem.at[1, s]))

        @pl.when(i == 0)
        def _visit():
            for s in range(sdx):
                @pl.when(blk_ref[s] >= 0)
                def _start():
                    for cp in dma(s):
                        cp.start()

            for s in range(sdx):
                @pl.when(blk_ref[s] >= 0)
                def _wait():
                    for cp in dma(s):
                        cp.wait()

            nnz_row = nnz_scr[0]                 # [1, B]
            budget = budget_ref[0]
            buf_row = state_ref[0, np_]
            eq0 = jnp.zeros((buf_row.shape[0], 1), jnp.int32)

            def count(act, nnz):
                return jnp.sum(jnp.where(act, nnz, 0), axis=1,
                               keepdims=True, dtype=jnp.int32)

            if name == "minplus":
                d0 = state_ref[0, 0]
                d1, _, alpha0, pending0, _ = frontier(buf_row, d0,
                                                      delta=window,
                                                      strict=strict)

                # boolean masks ride the loop as int32: Mosaic carries no
                # i1 vectors through scf.while
                def act_of(d, pending, eq):
                    return ((pending != 0) & (d <= alpha0 + window)
                            & (eq.astype(jnp.float32) < budget))

                def cond(c):
                    d, pending, emit, eq, rounds = c
                    return jnp.logical_and(
                        rounds < max_rounds,
                        jnp.any(act_of(d, pending, eq)))

                def body(c):
                    d, pending, emit, eq, rounds = c
                    act = act_of(d, pending, eq)
                    eq = eq + count(act, nnz_row)
                    src_scr[...] = jnp.where(act, d, INF)
                    nd = relax(src_scr, w_scr.at[0])
                    improved = nd < d
                    pending = ((pending != 0) & ~act) | improved
                    return (jnp.minimum(d, nd), pending.astype(jnp.int32),
                            ((emit != 0) | act).astype(jnp.int32), eq,
                            rounds + 1)

                d, pending, emit, eq, rounds = jax.lax.while_loop(
                    cond, body, (d1, pending0.astype(jnp.int32),
                                 jnp.zeros(d1.shape, jnp.int32), eq0,
                                 jnp.int32(0)))
                emask = emit != 0
                payload = jnp.where(emask, d, INF)
                keep = jnp.where(pending != 0, d, INF)
                new_planes = (d,)
            else:
                p0, r0 = state_ref[0, 0], state_ref[0, 1]
                degf = deg_row.astype(jnp.float32)
                degc = jnp.maximum(degf, 1.0)
                has_edges = degf > 0

                def act_of(r, eq):
                    return ((r >= eps * degc) & has_edges
                            & (eq.astype(jnp.float32) < budget))

                def cond(c):
                    pv, rv, av, eq, rounds = c
                    return jnp.logical_and(rounds < max_rounds,
                                           jnp.any(act_of(rv, eq)))

                def body(c):
                    pv, rv, av, eq, rounds = c
                    lane = eq.astype(jnp.float32) < budget
                    pv, rv, av, act = push(pv, rv, av, w_scr[0], degf,
                                           alpha=alpha, eps=eps,
                                           lane_mask=lane, spread=contrib)
                    eq = eq + count(act, nnz_row)
                    return pv, rv, av, eq, rounds + 1

                pv, rv, av, eq, rounds = jax.lax.while_loop(
                    cond, body, (p0, r0 + buf_row, jnp.zeros_like(r0),
                                 eq0, jnp.int32(0)))
                payload = av
                keep = jnp.zeros_like(rv)
                new_planes = (pv, rv)
                emask = av > 0

            # ---- emission prep: every neighbor contribution and the full
            # emission edge count in one shot ----
            if name == "minplus":
                src_scr[...] = payload
            for s in range(1, sdx):
                valid = blk_ref[s] >= 0
                cand_scr[s] = jnp.full(payload.shape, identity,
                                       payload.dtype)

                @pl.when(valid)
                def _contrib():
                    if name == "minplus":
                        cand_scr[s] = relax(src_scr, w_scr.at[s])
                    else:
                        cand_scr[s] = contrib(payload, w_scr[s])

                eq = eq + count(emask, jnp.where(valid, nnz_scr[s], 0))
            cand_scr[0] = keep
            plane_scr[0] = new_planes[prio_plane]
            deg_scr[0] = deg_row

            for k in range(np_):
                o_state_ref[0, k] = new_planes[k]
            o_state_ref[0, np_] = keep
            o_rounds_ref[0] = rounds
            o_eq_ref[...] = eq

        @pl.when(i > 0)
        def _emit():
            # an output block is never fetched, so the neighbor's whole
            # block is written: planes copied through, buffer combined
            for k in range(np_):
                o_state_ref[0, k] = state_ref[0, k]
            new_buf = combine(state_ref[0, np_], cand_scr[i])
            o_state_ref[0, np_] = new_buf
            cand_scr[i] = new_buf
            plane_scr[i] = state_ref[0, prio_plane]
            deg_scr[i] = deg_row

        @pl.when(i == dmax)
        def _refresh():
            # scheduler refresh over the visited row (slot 0) and every
            # touched neighbor — after the last combine, so it observes
            # the combined rows exactly like the XLA gather-after-scatter
            # refresh; was-empty reads the pre-visit entry.  Invalid slots
            # leave their entries unwritten; the scatter drops them.
            for s in range(sdx):
                @pl.when(blk_ref[s] >= 0)
                def _row():
                    pln = plane_scr[s]
                    planes = (pln,) if name == "minplus" else (pln, pln)
                    newprio, newops = prio_of(cand_scr[s], planes,
                                              deg_scr[s, 0])
                    fin = jnp.isfinite(newprio)
                    if s == 0:
                        stamp = jnp.where(fin, cnt, jnp.int32(_BIG_STAMP))
                    else:
                        was_empty = ~jnp.isfinite(prio_ref[s])
                        stamp = jnp.where(was_empty & fin, cnt,
                                          stamp_ref[s])
                    o_prio_ref[s] = newprio
                    o_ops_ref[s] = newops
                    o_stamp_ref[s] = stamp

    def graph(dg_) -> FusedGraph:
        return FusedGraph(
            blocks=dg_.blocks,
            nnz=dg_.row_nnz[:, None, :],
            nbr_part=dg_.nbr_part, nbr_blk=dg_.nbr_blk,
            diag_blk=dg_.diag_blk,
            deg=jnp.concatenate(
                [dg_.deg, jnp.zeros((1, B), dg_.deg.dtype)])[:, None, :],
            budget=jnp.concatenate(
                [dg_.edge_budget,
                 jnp.zeros((1,), jnp.float32)]).astype(jnp.float32))

    def pack(planes: Tuple[jax.Array, ...], buf: jax.Array,
             prio: jax.Array, ops_count: jax.Array,
             stamp: jax.Array) -> PackedState:
        zrow = jnp.zeros((1,) + buf.shape[1:], buf.dtype)
        state = jnp.stack(
            [jnp.concatenate([x, zrow]) for x in planes] + [buf], axis=1)
        return PackedState(
            state,
            jnp.concatenate([prio.astype(jnp.float32),
                             jnp.full((1,), jnp.inf, jnp.float32)]),
            jnp.concatenate([ops_count.astype(jnp.int32),
                             jnp.zeros((1,), jnp.int32)]),
            jnp.concatenate([stamp.astype(jnp.int32),
                             jnp.full((1,), _BIG_STAMP, jnp.int32)]))

    def unpack(pk: PackedState):
        planes = tuple(pk.state[:P, k] for k in range(np_))
        buf = pk.state[:, np_]
        return planes, buf, pk.prio[:P], pk.ops[:P], pk.stamp[:P]

    def visit(fg: FusedGraph, pk: PackedState, p, counter):
        Q = pk.state.shape[2]
        p = jnp.asarray(p, jnp.int32)
        valid = fg.nbr_part[p] >= 0
        rowb = jnp.concatenate(
            [p[None], jnp.where(valid, fg.nbr_part[p], P)]).astype(jnp.int32)
        blk = jnp.concatenate(
            [fg.diag_blk[p][None],
             jnp.where(valid, fg.nbr_blk[p], -1)]).astype(jnp.int32)
        cnt = jnp.asarray(counter, jnp.int32)[None]
        # invalid slots point past the tables: their scatter is dropped
        upd = jnp.where(blk >= 0, rowb, P + 1)

        def row_map(i, rb, bk, c):
            return (rb[i], 0, 0, 0)

        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        state_spec = pl.BlockSpec((1, C, Q, B), row_map)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(sdx,),
            in_specs=[
                state_spec, smem, smem, smem,
                pl.BlockSpec((1, 1, B), lambda i, rb, bk, c: (rb[i], 0, 0)),
                hbm, hbm,
            ],
            out_specs=[
                state_spec, smem, smem, smem, smem,
                pl.BlockSpec((Q, 1), lambda i, rb, bk, c: (0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((sdx, B, B), jnp.float32),
                            pltpu.VMEM((sdx, 1, B), jnp.int32),
                            pltpu.VMEM((Q, B), jnp.float32),
                            pltpu.VMEM((sdx, Q, B), jnp.float32),
                            pltpu.VMEM((sdx, Q, B), jnp.float32),
                            pltpu.VMEM((sdx, 1, B), jnp.int32),
                            pltpu.SemaphoreType.DMA((2, sdx))],
        )
        state, prio, ops, stamp, rounds, eq = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(pk.state.shape, pk.state.dtype),
                jax.ShapeDtypeStruct((sdx,), jnp.float32),
                jax.ShapeDtypeStruct((sdx,), jnp.int32),
                jax.ShapeDtypeStruct((sdx,), jnp.int32),
                jax.ShapeDtypeStruct((1,), jnp.int32),
                jax.ShapeDtypeStruct((Q, 1), jnp.int32),
            ],
            input_output_aliases={3: 0},
            compiler_params=compiler_params(("arbitrary",)),
            interpret=interpret,
        )(rowb, blk, cnt, pk.state, pk.prio[rowb], pk.stamp[rowb],
          fg.budget[p][None], fg.deg, fg.blocks, fg.nnz)
        return (PackedState(
            state, pk.prio.at[upd].set(prio, mode="drop"),
            pk.ops.at[upd].set(ops, mode="drop"),
            pk.stamp.at[upd].set(stamp, mode="drop")), rounds[0], eq[:, 0])

    return FusedVisit(graph=graph, pack=pack, visit=visit, unpack=unpack)
