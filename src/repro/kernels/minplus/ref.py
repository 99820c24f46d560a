"""Pure-jnp oracle for the tropical (min-plus) block relaxation.

``relax(d, W)[q, v] = min_u d[q, u] + W[u, v]``

This is the dense vectorized form of one edge-relaxation sweep of all Q queries
over a VMEM-resident partition block — the TPU adaptation of the paper's
"sequential algorithm on the cache-resident partition" (DESIGN.md §2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def minplus_ref(d: jax.Array, w: jax.Array, chunk: int = 128) -> jax.Array:
    """d: [Q, B] distances (+inf inactive). w: [B, B] weights (+inf absent).

    Chunked over the contraction dim so peak memory is Q*chunk*B, not Q*B*B.
    """
    q, b = d.shape
    if w.shape != (b, b):
        raise ValueError(
            f"weight block must be square [{b}, {b}] to match d "
            f"{(q, b)}; got {w.shape}")
    chunk = min(chunk, b)
    nchunk = -(-b // chunk)
    pad = nchunk * chunk - b
    if pad:
        d = jnp.pad(d, ((0, 0), (0, pad)), constant_values=jnp.inf)
        w = jnp.pad(w, ((0, pad), (0, 0)), constant_values=jnp.inf)
    dc = d.reshape(q, nchunk, chunk).transpose(1, 0, 2)      # [nc, Q, c]
    wc = w.reshape(nchunk, chunk, b)                         # [nc, c, B]

    def body(carry, xs):
        dd, ww = xs                                          # [Q, c], [c, B]
        cand = jnp.min(dd[:, :, None] + ww[None, :, :], axis=1)
        return jnp.minimum(carry, cand), None

    init = jnp.full((q, b), jnp.inf, dtype=d.dtype)
    out, _ = jax.lax.scan(body, init, (dc, wc))
    return out


def masked_matmul_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """PPR spread oracle: ``out[q, v] = sum_u x[q, u] * [w[u, v] finite]``."""
    mask = jnp.isfinite(w).astype(x.dtype)
    # full f32 contraction: TPU's default f32 matmul rounds x to bf16,
    # an error far above the eps the push algebra guarantees
    return jnp.dot(x, mask, precision=jax.lax.Precision.HIGHEST)
