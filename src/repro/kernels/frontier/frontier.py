"""Pallas TPU kernel for the Δ-window frontier selection + priority reduce.

One partition visit (engine.py) starts by consolidating the buffer into the
distance state and finding (a) which (query, vertex) ops are active under
the Δ-window / yielding rules and (b) the partition's next priority value.
Fused here so the [Q, B] buffer tile makes one HBM->VMEM trip:

    pending = isfinite(buf) & (buf <= dist)
    d1      = min(dist, buf)
    alpha_q = min_v (pending ? d1 : inf)            per-query best
    active  = pending & (d1 <= alpha_q + delta)
    srcs    = active ? d1 : inf
    prio    = min over tile of alpha_q              (SMEM scalar out)

Grid over query tiles; outputs (d1, srcs, per-tile prio row).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tpu import compiler_params, on_tpu

DEFAULT_Q_TILE = 128
INF = jnp.inf


def frontier_tile(buf: jax.Array, dist: jax.Array, *, delta: float,
                  strict: bool = False,
                  ) -> Tuple[jax.Array, jax.Array, jax.Array,
                             jax.Array, jax.Array]:
    """Δ-window frontier math over one resident [QT, B] tile, kernel-safe.

    Returns ``(d1, srcs, alpha, pending, active)`` with ``alpha`` kept
    [QT, 1] so the fused visit kernel (DESIGN.md §2.4) can re-derive the
    active set each inner round.  Expression-for-expression identical to
    the XLA ``minplus_algebra.begin`` math in ``core/visit.py`` — the
    basis for the fused path's bit-parity with the megastep oracle.
    ``strict`` mirrors ``minplus_algebra(strict=...)``: the zero-weight
    cc instantiation pends ops only on strict improvement (``buf < dist``)
    so equal label re-sends cannot livelock the visit loop.
    """
    pending = jnp.isfinite(buf) & ((buf < dist) if strict
                                   else (buf <= dist))
    d1 = jnp.minimum(dist, jnp.where(pending, buf, INF))
    alpha = jnp.min(jnp.where(pending, d1, INF), axis=1, keepdims=True)
    active = pending & (d1 <= alpha + delta)
    srcs = jnp.where(active, d1, INF)
    return d1, srcs, alpha, pending, active


def _frontier_kernel(buf_ref, dist_ref, o_d_ref, o_src_ref, o_prio_ref, *,
                     delta: float):
    d1, srcs, alpha, _, _ = frontier_tile(buf_ref[...], dist_ref[...],
                                          delta=delta)
    o_d_ref[...] = d1
    o_src_ref[...] = srcs
    o_prio_ref[...] = jnp.min(alpha, axis=1)        # [QT]


@functools.partial(jax.jit, static_argnames=("delta", "q_tile",
                                             "interpret"))
def frontier_pallas_call(buf, dist, *, delta: float,
                         q_tile: int = DEFAULT_Q_TILE,
                         interpret: Optional[bool] = None):
    """buf, dist: [Q, B] -> (d1 [Q, B], srcs [Q, B], prio_rows [Q]).

    ``interpret=None`` follows the ``on_tpu()`` autodetect the ``ops.py``
    wrapper uses, so a direct call can't silently run interpreted on TPU."""
    if interpret is None:
        interpret = not on_tpu()
    q, b = buf.shape
    qt = min(q_tile, q) if q % min(q_tile, q) == 0 else q
    grid = (q // qt,)
    return pl.pallas_call(
        functools.partial(_frontier_kernel, delta=delta),
        grid=grid,
        in_specs=[
            pl.BlockSpec((qt, b), lambda i: (i, 0)),
            pl.BlockSpec((qt, b), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((qt, b), lambda i: (i, 0)),
            pl.BlockSpec((qt, b), lambda i: (i, 0)),
            pl.BlockSpec((qt,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, b), buf.dtype),
            jax.ShapeDtypeStruct((q, b), buf.dtype),
            jax.ShapeDtypeStruct((q,), buf.dtype),
        ],
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(buf, dist)
