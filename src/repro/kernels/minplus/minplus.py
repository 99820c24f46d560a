"""Pallas TPU kernels for the intra-partition batched relaxation.

Two kernels, both tiled so one partition visit's working set is VMEM-resident
(the paper's "partition fits into LLC", DESIGN.md §2):

  minplus_kernel       out[q, v] = min_u d[q, u] + w[u, v]   (tropical semiring,
                       VPU; one SSSP/BFS relaxation sweep for a Q-tile of
                       queries against a [B, B] adjacency block)
  masked_matmul_kernel out[q, v] = sum_u x[q, u] * finite(w[u, v])  (MXU; the
                       PPR residual spread)

Tiling: grid over query tiles; the adjacency block [B, B] is broadcast to all
programs (one HBM->VMEM load amortized over Q/QT programs — the cache-reuse
argument of the paper in BlockSpec form).  The contraction runs one source
row ``u`` at a time over refs (:func:`minplus_relax`): a lane-aligned chunk
of ``d`` is loaded once, column ``u`` is picked out with a masked lane
reduction and broadcast against row ``u`` of ``w``, so no value is ever
sliced at a dynamic offset and no ``[Q, B, B]`` temporary exists.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tpu import compiler_params, on_tpu

DEFAULT_Q_TILE = 128
#: contraction chunk: one vreg's worth of lanes, the granularity at which
#: ``d`` is loaded and at which ``skip_inactive`` skips all-+inf sources
DEFAULT_U_CHUNK = 128


def _u_chunk(b: int, u_chunk: int) -> int:
    uc = min(int(u_chunk), b)
    return uc if b % uc == 0 else b


def minplus_relax(d_ref, w_ref, *, u_chunk: int = DEFAULT_U_CHUNK,
                  skip_inactive: bool = False) -> jax.Array:
    """``out[q, v] = min_u d_ref[q, u] + w_ref[u, v]`` over a resident tile.

    ``d_ref``: [QT, B] ref; ``w_ref``: a ref with at least B rows of width
    B (only rows ``0..B-1`` are read, so the fused visit's row-B-augmented
    adjacency view works unchanged).  Every candidate is the same f32 sum
    ``d[q, u] + w[u, v]`` and the reduction is an exact ``min``, so the
    result is bitwise equal to ``ref.minplus_ref`` whatever the chunking —
    the property the fused-visit parity harness pins.

    ``skip_inactive=True`` guards each chunk with a ``lax.cond`` on
    ``any(isfinite(d_chunk))``: a chunk whose source columns are all +inf
    can only contribute +inf candidates, so skipping it is bit-identical
    while a late sparse frontier skips most of the compute (the fused
    visit's sparse-frontier mode, DESIGN.md §2.4).
    """
    qt, b = d_ref.shape
    uc = _u_chunk(b, u_chunk)
    lane = jax.lax.broadcasted_iota(jnp.int32, (qt, uc), 1)

    def chunk(c, acc):
        u0 = pl.multiple_of(c * uc, uc)
        dc = d_ref[:, pl.ds(u0, uc)]

        def relax(acc):
            def one(j, acc):
                col = jnp.min(jnp.where(lane == j, dc, jnp.inf), axis=1,
                              keepdims=True)
                return jnp.minimum(acc, col + w_ref[pl.ds(u0 + j, 1), :])
            return jax.lax.fori_loop(0, uc, one, acc)

        if skip_inactive:
            return jax.lax.cond(jnp.any(jnp.isfinite(dc)), relax,
                                lambda a: a, acc)
        return relax(acc)

    acc0 = jnp.full((qt, b), jnp.inf, dtype=d_ref.dtype)
    return jax.lax.fori_loop(0, b // uc, chunk, acc0)


def _minplus_kernel(d_ref, w_ref, o_ref, *, u_chunk: int):
    o_ref[...] = minplus_relax(d_ref, w_ref, u_chunk=u_chunk)


def _masked_matmul_kernel(x_ref, w_ref, o_ref):
    mask = jnp.isfinite(w_ref[...]).astype(x_ref.dtype)
    o_ref[...] = jnp.dot(x_ref[...], mask,
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=x_ref.dtype)


def _tile(q: int, q_tile: int) -> int:
    """Resolve the query-tile size; Q must divide into whole tiles.

    A non-dividing Q used to silently collapse the grid to one [Q, B]
    program, defeating the tiling (and the VMEM working-set bound) exactly
    when Q grew past the tile.  ``ops.py`` already pads the query axis with
    the mode identity, so inside this module divisibility is a contract,
    not a fallback.
    """
    qt = min(q_tile, q)
    if q % qt != 0:
        raise ValueError(
            f"Q={q} does not divide into q_tile={qt} tiles; pad the query "
            f"axis to a tile multiple first (repro.kernels.minplus.ops pads "
            f"with the mode identity)")
    return qt


@functools.partial(jax.jit, static_argnames=("q_tile", "u_chunk",
                                             "interpret"))
def minplus_pallas_call(d: jax.Array, w: jax.Array,
                        q_tile: int = DEFAULT_Q_TILE,
                        u_chunk: int = DEFAULT_U_CHUNK,
                        interpret: Optional[bool] = None) -> jax.Array:
    """d: [Q, B], w: [B, B] -> [Q, B].  Q must divide by the chosen tile
    (ops.py pads); a ``u_chunk`` that does not divide B falls back to one
    chunk.

    ``interpret=None`` follows the same :func:`~repro.kernels.tpu.on_tpu`
    autodetect the ``ops.py`` wrappers use, so a direct call can't
    silently run interpreted on TPU."""
    if interpret is None:
        interpret = not on_tpu()
    q, b = d.shape
    qt = _tile(q, q_tile)
    grid = (q // qt,)
    return pl.pallas_call(
        functools.partial(_minplus_kernel, u_chunk=u_chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((qt, b), lambda i: (i, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((qt, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, b), d.dtype),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(d, w)


@functools.partial(jax.jit, static_argnames=("q_tile", "interpret"))
def masked_matmul_pallas_call(x: jax.Array, w: jax.Array,
                              q_tile: int = DEFAULT_Q_TILE,
                              interpret: Optional[bool] = None) -> jax.Array:
    if interpret is None:
        interpret = not on_tpu()
    q, b = x.shape
    qt = _tile(q, q_tile)
    grid = (q // qt,)
    return pl.pallas_call(
        _masked_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((qt, b), lambda i: (i, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((qt, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, b), x.dtype),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(x, w)
