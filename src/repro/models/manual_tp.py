"""Manual (shard_map) tensor-parallel blocks — the §Perf hillclimb fix.

GSPMD's auto-partitioner mishandles 2-D-sharded weight gradients under our
layouts: it either materializes full-size f32 dW per chip (~10 x 1.3 GB
live buffers, all-reduce over "model") or — with the gather-in constraint —
computes dW fully replicated (+2.3x layer FLOPs).  Both measured in
EXPERIMENTS.md §Perf.

These blocks pin the Megatron partitioning by construction: the "model"
axis is *manual* (shard_map), so

    fwd:  h_loc = x @ wi_loc          (F sharded; no comm)
          y     = psum(h_loc @ wo_loc, "model")
    bwd:  dW_loc = x^T @ dh_loc        local [d, F/TP] — never full-size

while "data"/"pod" stay auto: FSDP gathers/reduce-scatters over "data" are
still inserted by GSPMD around the local weights.  Enabled per-arch via
rules["manual_tp"] when the head/ff dims divide the model axis; the auto
path remains the fallback (and the measured baseline).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models import attention as attn_lib
from repro.models.layers import _act, apply_rope


def _tp(rules):
    mesh = rules.mesh
    if "model" not in mesh.axis_names:
        return 1
    return mesh.devices.shape[mesh.axis_names.index("model")]


def mlp_eligible(cfg, rules) -> bool:
    tp = _tp(rules)
    return tp > 1 and cfg.d_ff % tp == 0


def attn_eligible(cfg, rules) -> bool:
    tp = _tp(rules)
    if tp <= 1 or cfg.n_heads % tp:
        return False
    h_loc = cfg.n_heads // tp
    g = cfg.n_heads // cfg.n_kv_heads
    # per-shard q heads must align with whole kv-head groups
    return (cfg.n_kv_heads % tp == 0) or \
        (tp % cfg.n_kv_heads == 0 and g % h_loc == 0)


def manual_mlp(lp, x, cfg, rules):
    """x: [B,S,D] -> [B,S,D].  F manually sharded over "model"."""
    mesh = rules.mesh
    gated = "wg" in lp

    cdt = x.dtype

    def local(wi, wo, wg, x32):
        # x crosses the boundary in f32 so its cotangent psum (inserted by
        # the shard_map transpose for a replicated input) is f32 — a bf16
        # all-reduce hard-aborts XLA:CPU's AllReducePromotion pass
        x = x32.astype(cdt)
        h = jnp.einsum("bsd,df->bsf", x, wi.astype(x.dtype))
        h = _act(h, cfg.act)
        if gated:
            h = h * jnp.einsum("bsd,df->bsf", x, wg.astype(x.dtype))
        y = jnp.einsum("bsf,fd->bsd", h, wo.astype(x.dtype))
        # psum in f32: better numerics, and XLA:CPU's AllReducePromotion
        # pass crashes on bf16 all-reduce (hard abort)
        return jax.lax.psum(y.astype(jnp.float32), "model").astype(x.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        # auto axes ("data"/"pod") may not appear in specs: the batch dim's
        # FSDP/DP sharding passes through shard_map untouched
        in_specs=(P(None, "model"), P("model", None), P(None, "model"),
                  P(None, None, None)),
        out_specs=P(None, None, None),
        axis_names={"model"}, check_vma=False)(
            lp["wi"], lp["wo"], lp.get("wg", lp["wi"]),
            x.astype(jnp.float32))


def manual_attention(lp, x, positions, cfg, rules, *, window=None,
                     prefix_len=None):
    """x: [B,S,D] -> attention output [B,S,D] (pre-residual).

    Q heads manually sharded over "model"; KV heads sharded when divisible,
    otherwise computed from replicated KV weights and sliced to the one
    whole kv-group this shard's q heads belong to.
    """
    mesh = rules.mesh
    tp = _tp(rules)
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    h_loc = H // tp
    kv_sharded = Hkv % tp == 0
    kv_loc = Hkv // tp if kv_sharded else max(1, h_loc * Hkv // H)
    has_bias = "bq" in lp

    cdt = x.dtype
    kv_hd_sharded = (not kv_sharded) and hd % tp == 0

    def local(wq, wk, wv, wo, bq, bk, bv, x32):
        x = x32.astype(cdt)   # f32 boundary: see manual_mlp
        idx = jax.lax.axis_index("model")
        q = jnp.einsum("bsd,dhk->bshk", x, wq.astype(x.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x, wk.astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, wv.astype(x.dtype))
        if kv_hd_sharded:
            # kv projections computed sharded over head_dim, then the
            # (small) result gathered: avoids computing k/v fully
            # replicated on every shard (+0.8e12 FLOPs/layer measured).
            # f32 wire: the gather's transpose is a reduce-scatter, and a
            # bf16 reduce-scatter aborts XLA:CPU (AllReducePromotion bug)
            k = jax.lax.all_gather(k.astype(jnp.float32), "model",
                                   axis=3, tiled=True).astype(x.dtype)
            v = jax.lax.all_gather(v.astype(jnp.float32), "model",
                                   axis=3, tiled=True).astype(x.dtype)
        if has_bias:
            q = q + bq.astype(x.dtype)
            k = k + bk.astype(x.dtype)
            v = v + bv.astype(x.dtype)
        if not kv_sharded and Hkv > kv_loc:
            # slice the kv group(s) serving this shard's q heads
            start = (idx * h_loc * Hkv) // H
            k = jax.lax.dynamic_slice_in_dim(k, start, kv_loc, axis=2)
            v = jax.lax.dynamic_slice_in_dim(v, start, kv_loc, axis=2)
        if cfg.rope_theta:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        o = attn_lib.attend(q, k, v, positions, positions, causal=True,
                            window=window, prefix_len=prefix_len)
        y = jnp.einsum("bshk,hkd->bsd", o, wo.astype(x.dtype))
        return jax.lax.psum(y.astype(jnp.float32), "model").astype(x.dtype)

    zeros = jnp.zeros((1,), x.dtype)
    if kv_sharded:
        kvspec, kvb = P(None, "model", None), P("model", None)
    elif kv_hd_sharded:
        kvspec, kvb = P(None, None, "model"), P(None, None)
    else:
        kvspec, kvb = P(None, None, None), P(None, None)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, "model", None), kvspec, kvspec,
                  P("model", None, None),
                  P("model", None) if has_bias else P(None),
                  kvb if has_bias else P(None),
                  kvb if has_bias else P(None),
                  P(None, None, None)),
        out_specs=P(None, None, None),
        axis_names={"model"}, check_vma=False)(
            lp["wq"], lp["wk"], lp["wv"], lp["wo"],
            lp.get("bq", zeros), lp.get("bk", zeros), lp.get("bv", zeros),
            x.astype(jnp.float32))
