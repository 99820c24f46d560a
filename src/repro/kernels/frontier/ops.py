"""Jitted wrapper: pad Q, dispatch kernel/ref by backend."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.contract import KernelContract, TileSpec
from repro.kernels.frontier.frontier import (frontier_pallas_call,
                                             frontier_tile)
from repro.kernels.frontier.ref import frontier_ref
from repro.kernels.tpu import on_tpu

#: static contract (DESIGN.md §7): canonical B=64 instantiation, tiled
#: q_tile=32 so the per-step footprint stays inside the planner model's
#: working set.  Wired: ``frontier_tile`` is the round-0 consolidation of
#: the fused visit kernel (core/visit.make_megastep(fused=True)), and the
#: standalone pallas_call remains callable directly.
CONTRACTS = (
    KernelContract(
        name="frontier", module="repro.kernels.frontier.frontier",
        grid=(2,),
        in_tiles=(TileSpec("buf", (64, 64), (32, 64)),
                  TileSpec("dist", (64, 64), (32, 64))),
        out_tiles=(TileSpec("d1", (64, 64), (32, 64)),
                   TileSpec("srcs", (64, 64), (32, 64)),
                   TileSpec("prio", (64,), (32,))),
        wired=True,
        block_size=64, num_queries=64),
)

__all__ = ["CONTRACTS", "frontier", "frontier_pallas", "frontier_tile"]


def frontier(buf, dist, *, delta: float):
    return frontier_ref(buf, dist, delta=delta)


def frontier_pallas(buf, dist, *, delta: float, interpret=None):
    if interpret is None:
        interpret = not on_tpu()
    q, b = buf.shape
    pad = (-q) % 8
    if pad:
        buf = jnp.pad(buf, [(0, pad), (0, 0)], constant_values=jnp.inf)
        dist = jnp.pad(dist, [(0, pad), (0, 0)], constant_values=jnp.inf)
    d1, srcs, prio = frontier_pallas_call(buf, dist, delta=delta,
                                          interpret=interpret)
    return d1[:q], srcs[:q], prio[:q]
