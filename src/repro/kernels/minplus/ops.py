"""Dispatching wrappers for the intra-partition relaxation primitives.

``minplus`` / ``masked_matmul``  — pure-jnp (XLA) paths, the default on CPU.
``minplus_ell`` — the min-plus relaxation over a block's pull-ELL slice
(``BlockGraph.ell_src`` / ``ell_w``) instead of its dense tile; XLA.
``minplus_pallas`` / ``masked_matmul_pallas`` — Pallas kernels; on TPU they
compile natively, elsewhere they run in interpret mode (correct but slow, used
by the kernel test sweeps).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.contract import KernelContract, TileSpec
from repro.kernels.minplus import minplus as _k
from repro.kernels.minplus.ref import masked_matmul_ref, minplus_ref
from repro.kernels.tpu import on_tpu

#: static contracts (DESIGN.md §7): canonical instantiation at the
#: planner's smallest block B=64 with a full Q=64 query tile (qt = min(
#: DEFAULT_Q_TILE, Q) = 64, grid collapses to one program).  Both kernels
#: are wired: core/visit and core/baselines dispatch them per visit.
CONTRACTS = (
    KernelContract(
        name="minplus", module="repro.kernels.minplus.minplus",
        grid=(1,),
        in_tiles=(TileSpec("d", (64, 64), (64, 64)),
                  TileSpec("w", (64, 64), (64, 64))),
        out_tiles=(TileSpec("out", (64, 64), (64, 64)),),
        wired=True, block_size=64, num_queries=64),
    KernelContract(
        name="masked_matmul", module="repro.kernels.minplus.minplus",
        grid=(1,),
        in_tiles=(TileSpec("x", (64, 64), (64, 64)),
                  TileSpec("w", (64, 64), (64, 64))),
        out_tiles=(TileSpec("out", (64, 64), (64, 64)),),
        wired=True, block_size=64, num_queries=64),
)


def minplus(d: jax.Array, w: jax.Array) -> jax.Array:
    return minplus_ref(d, w)


def masked_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    return masked_matmul_ref(x, w)


def minplus_ell(d: jax.Array, src: jax.Array, w: jax.Array) -> jax.Array:
    """``out[q, v] = min_j d[q, src[v, j]] + w[v, j]`` for d [Q, B] and a
    pull-ELL slice src/w [B, W] (padding: any row, weight +inf).

    The same f32 sums over the same finite candidates as
    ``minplus(d, dense)``, and a min is exact in any order, so the bits
    equal the dense relaxation's.  One row gather of the ``[B, Q]``
    transpose per slot, folded by ``min``: on a v5e (Q = 64, B = 1024,
    W = 4) 13.1 us a relaxation, against 18.8 us for one ``[B, W, Q]``
    gather, 19.5 us for ``take`` along the lane axis and 92.0 us for the
    dense tile (PERF.md §6).
    """
    dt = d.T
    out = dt[src[:, 0]] + w[:, 0, None]
    for j in range(1, src.shape[1]):
        out = jnp.minimum(out, dt[src[:, j]] + w[:, j, None])
    return out.T


def _pad_q(x: jax.Array, tile: int, identity: float):
    """Pad the query axis to a tile multiple with the *mode identity*
    (``+inf`` for min-plus, ``0`` for the masked matmul) so padded rows are
    inert under the kernel's combine and the kernel can require exact
    divisibility (minplus._tile) instead of silently un-tiling."""
    q = x.shape[0]
    if q % tile == 0 or q < tile:
        return x, q
    pad = (-q) % tile
    return jnp.pad(x, ((0, pad), (0, 0)), constant_values=identity), q


def minplus_pallas(d: jax.Array, w: jax.Array, q_tile: int = 128) -> jax.Array:
    dp, q = _pad_q(d, q_tile, jnp.inf)
    out = _k.minplus_pallas_call(dp, w, q_tile=q_tile,
                                 interpret=not on_tpu())
    return out[:q]


def masked_matmul_pallas(x: jax.Array, w: jax.Array,
                         q_tile: int = 128) -> jax.Array:
    xp, q = _pad_q(x, q_tile, 0.0)
    out = _k.masked_matmul_pallas_call(xp, w, q_tile=q_tile,
                                       interpret=not on_tpu())
    return out[:q]
