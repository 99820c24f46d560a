"""Dispatch + static contracts for the fused Pallas visit kernel.

``core/visit.make_megastep(fused=True)`` imports :func:`make_fused_visit`
from here and hands it the ``frontier_tile`` / ``push_tile`` inner ops —
the dispatch table stays in ``core/``, the VMEM choreography stays here.

Two canonical contracts are declared, one per algebra, on the tier-1
canonical graph instantiation (grid2d 16x16, B = 64 -> P = 4 partitions,
dmax = 2 neighbor slots, Q = 64 query lanes).  The kernel runs over the
*packed* state layout (``fused.PackedState``):

  * ``state`` [P+1, C, Q, B] f32 — the C = num_planes+1 value planes plus
    the buffered-ops row as channels of one array (row P = trash), so a
    grid step schedules ONE state fetch + ONE write-back instead of 2C+2
    of them — the packing is the perf, not a convenience.  The output is
    aliased onto the input and written at scalar-prefetched row indices
    (``update="rmw"``: the index map owns coverage);
  * ``prio`` / ``stamp`` [1+dmax] in, ``prio`` / ``ops`` / ``stamp``
    [1+dmax] out — the scheduler entries of the rows the visit touches,
    in SMEM, refreshed entry by entry on the last grid step
    (``update="accum"``: one block, not a tiling) and scattered back into
    the [P+1] tables outside the kernel; ``budget`` [1] is the visited
    partition's edge budget;
  * ``deg`` [P+1, 1, B] — the degree row of the partition each step
    touches;
  * ``rounds`` [1] and ``eq`` [Q, 1] — the visit's round counter and exact
    per-query edge counters (``update="accum"``).

The adjacency is not a BlockSpec operand: the visited partition's blocks
(diagonal + ``dmax`` boundary) and their per-row edge counts stay in HBM
and are DMA'd into VMEM scratch by block id.  That scratch and the
per-slot emission parking (two [Q, B] planes + a degree row per slot) ride
on top of the BlockSpec footprint; ``MemoryModel.fused_working_set``
(``fused_model=True``) budgets all of it, since holding a whole visit
resident is the point.
"""
from __future__ import annotations

from repro.kernels.contract import KernelContract, TileSpec
from repro.kernels.fused_visit.fused import make_fused_visit

_SLOTS = dict(full=(3,), block=(3,))      # one SMEM entry per grid slot


def _contract(name: str, num_planes: int) -> KernelContract:
    c = num_planes + 1
    return KernelContract(
        name=name, module="repro.kernels.fused_visit.fused",
        grid=(3,),                       # 1 resident visit + dmax=2 emits
        in_tiles=(TileSpec("state", (5, c, 64, 64), (1, c, 64, 64)),
                  TileSpec("prio", **_SLOTS),
                  TileSpec("stamp", **_SLOTS),
                  TileSpec("budget", (1,), (1,)),
                  TileSpec("deg", (5, 1, 64), (1, 1, 64))),
        out_tiles=(TileSpec("state1", (5, c, 64, 64), (1, c, 64, 64),
                            update="rmw"),
                   TileSpec("prio1", **_SLOTS, update="accum"),
                   TileSpec("ops1", **_SLOTS, update="accum"),
                   TileSpec("stamp1", **_SLOTS, update="accum"),
                   TileSpec("rounds", (1,), (1,), update="accum"),
                   TileSpec("eq", (64, 1), (64, 1), update="accum")),
        wired=True, block_size=64, num_queries=64,
        fused_model=True, num_planes=num_planes)


CONTRACTS = (_contract("fused_visit_minplus", 1),
             _contract("fused_visit_push", 2))

__all__ = ["CONTRACTS", "make_fused_visit"]
