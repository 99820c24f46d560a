"""Pallas TPU kernel: one fused PPR push round over a VMEM-resident block.

The push-mode engine (engine.make_push_visit) does, per inner round:

    active = (r >= eps*deg) & has_edges
    p     += alpha * r * active
    push   = (1-alpha) * r * active / deg
    r      = r*(1-active) + push @ A_mask
    acc   += push

Unfused, that is 5 HBM round-trips over [Q, B] tensors; fused here the
tile is loaded once (DESIGN.md §2 — the VMEM-residency argument).  The
spread matmul runs on the MXU via the finite-mask of the weight block.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tpu import compiler_params, on_tpu

DEFAULT_Q_TILE = 128


def push_tile(p: jax.Array, r: jax.Array, acc: jax.Array, w: jax.Array,
              deg: jax.Array, *, alpha: float, eps: float,
              lane_mask: Optional[jax.Array] = None,
              spread: Optional[Callable] = None,
              ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One PPR push round over a resident tile, kernel-safe.

    ``p, r, acc``: [QT, B]; ``w``: [B, B] (+inf = absent); ``deg``: [B] or
    broadcastable float row.  Returns ``(p1, r1, acc1, active)``.

    ``lane_mask`` (bool, broadcastable to [QT, B]) further gates the
    active set — the fused visit kernel (DESIGN.md §2.4) passes the
    per-query edge-budget lane there.  ``spread`` replaces the default
    masked matmul (``push @ finite(w)``): the fused path passes the
    algebra's ``contrib`` so both paths run the identical f32 contraction
    and stay bit-identical to the XLA megastep.
    """
    deg = jnp.asarray(deg, r.dtype)
    degc = jnp.maximum(deg, 1.0)
    has_edges = deg > 0
    active = (r >= eps * degc) & has_edges
    if lane_mask is not None:
        active = active & lane_mask
    af = active.astype(r.dtype)
    p1 = p + alpha * r * af
    push = (1.0 - alpha) * r * af / degc
    if spread is None:
        mask = jnp.isfinite(w).astype(r.dtype)
        sp = jnp.dot(push, mask, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=r.dtype)
    else:
        sp = spread(push, w)
    r1 = r * (1.0 - af) + sp
    acc1 = acc + push
    return p1, r1, acc1, active


def _push_kernel(p_ref, r_ref, acc_ref, w_ref, deg_ref, o_p, o_r, o_acc,
                 *, alpha: float, eps: float):
    p1, r1, acc1, _ = push_tile(p_ref[...], r_ref[...], acc_ref[...],
                                w_ref[...], deg_ref[...],
                                alpha=alpha, eps=eps)
    o_p[...] = p1
    o_r[...] = r1
    o_acc[...] = acc1


@functools.partial(jax.jit, static_argnames=("alpha", "eps", "q_tile",
                                             "interpret"))
def ppr_push_pallas_call(p, r, acc, w, deg, *, alpha: float, eps: float,
                         q_tile: int = DEFAULT_Q_TILE,
                         interpret: Optional[bool] = None):
    """p, r, acc: [Q, B]; w: [B, B] (+inf absent); deg: [1, B] float.

    ``interpret=None`` follows the ``on_tpu()`` autodetect the ``ops.py``
    wrapper uses, so a direct call can't silently run interpreted on TPU."""
    if interpret is None:
        interpret = not on_tpu()
    q, b = p.shape
    qt = min(q_tile, q) if q % min(q_tile, q) == 0 else q
    grid = (q // qt,)
    tile = pl.BlockSpec((qt, b), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_push_kernel, alpha=alpha, eps=eps),
        grid=grid,
        in_specs=[tile, tile, tile,
                  pl.BlockSpec((b, b), lambda i: (0, 0)),
                  pl.BlockSpec((1, b), lambda i: (0, 0))],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((q, b), p.dtype)] * 3,
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(p, r, acc, w, deg)
