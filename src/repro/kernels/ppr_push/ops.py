"""Jitted wrapper for the fused PPR push kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.contract import KernelContract, TileSpec
from repro.kernels.ppr_push.push import ppr_push_pallas_call, push_tile
from repro.kernels.ppr_push.ref import ppr_push_ref
from repro.kernels.tpu import on_tpu

#: static contract (DESIGN.md §7): canonical B=64 instantiation, tiled
#: q_tile=16 so the per-step footprint (three state planes in and out
#: plus the weight block) stays inside the planner model's working set.
#: Wired: ``push_tile`` is the inner-round body of the fused visit kernel
#: (core/visit.make_megastep(fused=True)) for push-mode PPR, and the
#: standalone pallas_call remains callable directly.
CONTRACTS = (
    KernelContract(
        name="ppr_push", module="repro.kernels.ppr_push.push",
        grid=(4,),
        in_tiles=(TileSpec("p", (64, 64), (16, 64)),
                  TileSpec("r", (64, 64), (16, 64)),
                  TileSpec("acc", (64, 64), (16, 64)),
                  TileSpec("w", (64, 64), (64, 64)),
                  TileSpec("deg", (1, 64), (1, 64))),
        out_tiles=(TileSpec("p1", (64, 64), (16, 64)),
                   TileSpec("r1", (64, 64), (16, 64)),
                   TileSpec("acc1", (64, 64), (16, 64))),
        wired=True,
        block_size=64, num_queries=64),
)

__all__ = ["CONTRACTS", "ppr_push", "ppr_push_pallas", "push_tile"]


def ppr_push(p, r, acc, w, deg, *, alpha: float, eps: float):
    return ppr_push_ref(p, r, acc, w, deg, alpha=alpha, eps=eps)


def ppr_push_pallas(p, r, acc, w, deg, *, alpha: float, eps: float,
                    interpret=None):
    if interpret is None:
        interpret = not on_tpu()
    if deg.ndim == 1:
        deg = deg[None, :]
    deg = deg.astype(p.dtype)
    q = p.shape[0]
    pad = (-q) % 8
    if pad:
        widths = [(0, pad), (0, 0)]
        p, r, acc = (jnp.pad(x, widths) for x in (p, r, acc))
    po, ro, ao = ppr_push_pallas_call(p, r, acc, w, deg, alpha=alpha,
                                      eps=eps, interpret=interpret)
    return po[:q], ro[:q], ao[:q]
