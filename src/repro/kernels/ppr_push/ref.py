"""Pure-jnp oracle for the fused PPR push kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ppr_push_ref(p, r, acc, w, deg, *, alpha: float, eps: float):
    degc = jnp.maximum(deg, 1.0)
    has_edges = deg > 0
    active = (r >= eps * degc) & has_edges
    af = active.astype(r.dtype)
    p_out = p + alpha * r * af
    push = (1.0 - alpha) * r * af / degc
    mask = jnp.isfinite(w).astype(r.dtype)
    spread = jnp.dot(push, mask, precision=jax.lax.Precision.HIGHEST)
    r_out = r * (1.0 - af) + spread
    return p_out, r_out, acc + push
