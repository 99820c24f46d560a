"""Attention for the LM zoo.

Three execution paths:

* ``attend``        — chunked online-softmax attention (lax.scan over KV
  chunks).  Used for train/prefill at any sequence length: the [Sq, Skv]
  score matrix never materializes beyond one [Sq, C] chunk.  This is the
  XLA-level twin of the Pallas flash kernel (kernels/flash_attention), which
  replaces it on real TPUs.
* ``decode_attend_partitioned`` — one-token decode against a KV cache whose
  *sequence* dim is sharded over the "model" mesh axis.  Each shard computes
  partial (max, exp-sum, weighted-V) for its resident KV partition and the
  partials combine with a log-sum-exp psum.  This is the paper's buffered
  execution model applied to serving: B independent queries (sequences) ride
  the batch dim, the shared partitioned structure is the KV cache, and the
  boundary-op exchange of Alg. 2 line 16 is the psum (DESIGN.md §4.1).
* ``decode_attend_local`` — same math on an unsharded cache (CPU tests,
  window attention whose cache is a small ring buffer).

GQA throughout: Hkv kv-heads are broadcast over group = H // Hkv query heads.
Head layout in all einsums: h = kv-head, g = group (so h*g = H).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.layers import _normal, apply_rope

NEG = -1e9  # mask value: large-negative (never -inf: exp() stays NaN-free)

# Probe override (launch/probes.py): cost_analysis counts a lax.scan body
# once, so probes compile attention with chunk >= Skv (single unrolled
# chunk) to make score FLOPs trip-count-exact.  None = use caller's chunk.
CHUNK_OVERRIDE = None


# ---------------------------------------------------------------------------
# params


def init_attention(key, d, n_heads, n_kv, head_dim, dtype, qkv_bias=False):
    ks = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d)
    so = 1.0 / np.sqrt(n_heads * head_dim)
    p = {"wq": _normal(ks[0], (d, n_heads, head_dim), dtype, s),
         "wk": _normal(ks[1], (d, n_kv, head_dim), dtype, s),
         "wv": _normal(ks[2], (d, n_kv, head_dim), dtype, s),
         "wo": _normal(ks[3], (n_heads, head_dim, d), dtype, so)}
    a = {"wq": ("embed", "heads", "head_dim"),
         "wk": ("embed", "kv_heads", "head_dim"),
         "wv": ("embed", "kv_heads", "head_dim"),
         "wo": ("heads", "head_dim", "embed")}
    if qkv_bias:
        p.update(bq=jnp.zeros((n_heads, head_dim), dtype),
                 bk=jnp.zeros((n_kv, head_dim), dtype),
                 bv=jnp.zeros((n_kv, head_dim), dtype))
        a.update(bq=("heads", "head_dim"), bk=("kv_heads", "head_dim"),
                 bv=("kv_heads", "head_dim"))
    return p, a


def qkv_proj(p, x, positions, rope_theta):
    """x: [B,S,D] -> q [B,S,H,hd], k/v [B,S,Hkv,hd] (RoPE applied)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if rope_theta:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


def out_proj(p, o):
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))


# ---------------------------------------------------------------------------
# chunked online-softmax attention (train / prefill)


def attend(q, k, v, q_pos, kv_pos, *, causal=True,
           window: Optional[int] = None, chunk: int = 1024,
           kv_mask=None, prefix_len: Optional[int] = None) -> jax.Array:
    """q: [B,Sq,H,hd]; k,v: [B,Skv,Hkv,hd]; q_pos: [Sq]; kv_pos: [Skv].

    Returns [B,Sq,H,hd].  Scans over ceil(Skv/chunk) KV chunks carrying the
    online-softmax state; peak score memory is [B,H,Sq,chunk].
    kv_mask: optional [B,Skv] bool validity (e.g. stub-frontend padding).
    prefix_len: positions < prefix_len are attendable by everyone
    (prefix-LM / vlm image prefix).
    """
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    scale = 1.0 / np.sqrt(hd)
    if CHUNK_OVERRIDE is not None:
        chunk = CHUNK_OVERRIDE
    chunk = min(chunk, Skv)
    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        padk = [(0, 0), (0, pad), (0, 0), (0, 0)]
        k, v = jnp.pad(k, padk), jnp.pad(v, padk)
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=-(10 ** 9))
        if kv_mask is not None:
            kv_mask = jnp.pad(kv_mask, [(0, 0), (0, pad)])
    # [B,Hkv,g,Sq,hd]
    qt = (jnp.transpose(q, (0, 2, 1, 3)).astype(jnp.float32)
          .reshape(B, Hkv, group, Sq, hd))
    kc = k.reshape(B, n_chunks, chunk, Hkv, hd).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(B, n_chunks, chunk, Hkv, hd).transpose(1, 0, 3, 2, 4)
    pc = kv_pos.reshape(n_chunks, chunk)
    mc = (kv_mask.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
          if kv_mask is not None else None)

    def step(carry, xs):
        m, l, acc = carry                        # [B,Hkv,g,Sq](,hd)
        kj, vj, pj, mkj = xs                     # [B,Hkv,C,hd], [C], [B,C]
        s = jnp.einsum("bhgqd,bhcd->bhgqc", qt,
                       kj.astype(jnp.float32)) * scale
        mask = (pj[None, :] <= q_pos[:, None]) if causal else \
            jnp.ones((Sq, chunk), bool)
        if window is not None:
            mask = mask & (pj[None, :] > q_pos[:, None] - window)
        if prefix_len is not None:
            mask = mask | (pj[None, :] < prefix_len)
        mask = mask & (pj >= 0)[None, :]
        cm = mask[None] if mkj is None else (mask[None] & mkj[:, None, :])
        cm = cm[:, None, None]                   # [B?,1,1,Sq,C]
        s = jnp.where(cm, s, NEG)
        mj = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, mj)
        r = jnp.exp(m - m_new)
        p = jnp.where(cm, jnp.exp(s - m_new[..., None]), 0.0)
        l = l * r + jnp.sum(p, axis=-1)
        o = jnp.einsum("bhgqc,bhcd->bhgqd", p, vj.astype(jnp.float32))
        acc = acc * r[..., None] + o
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, group, Sq), NEG, jnp.float32)
    l0 = jnp.zeros((B, Hkv, group, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, group, Sq, hd), jnp.float32)
    xs = (kc, vc, pc, mc)
    # flash-attention backward: recompute per-chunk scores/probabilities
    # instead of saving [*, Sq, chunk] residuals per chunk for the bwd
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, a0), xs)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# decode (one new token against a cache)


def _decode_partial(q, k, v, kv_pos, length, window):
    """Partial attention over one KV partition.

    q: [B,H,hd]; k,v: [B,C,Hkv,hd]; kv_pos: [C] absolute slot positions;
    length: [B] cache fill.  Returns (m, l, acc): [B,H], [B,H], [B,H,hd] —
    the partition's "boundary ops".
    """
    B, H, hd = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    scale = 1.0 / np.sqrt(hd)
    qf = q.reshape(B, Hkv, group, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bhgd,bchd->bhgc", qf, kf) * scale     # [B,Hkv,g,C]
    valid = kv_pos[None, :] < length[:, None]             # [B,C]
    if window is not None:
        valid = valid & (kv_pos[None, :] >= length[:, None] - window)
    vmask = valid[:, None, None, :]
    s = jnp.where(vmask, s, NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.where(vmask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgc,bchd->bhgd", p, vf)
    return m.reshape(B, H), l.reshape(B, H), acc.reshape(B, H, hd)


def combine_partials(m, l, acc, axis_name):
    """LSE-combine partial attention over ``axis_name`` (the partition axis).

    This is Alg. 2 line 16 for the serving FPP: each partition emits its
    buffered partial ops; one batched exchange (psum) consolidates them.
    """
    m_g = jax.lax.pmax(m, axis_name)
    r = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * r, axis_name)
    acc_g = jax.lax.psum(acc * r[..., None], axis_name)
    return acc_g / jnp.maximum(l_g[..., None], 1e-30)


def decode_attend_local(q, k, v, kv_pos, length, window=None):
    """Unsharded decode attention.  q: [B,H,hd] -> [B,H,hd]."""
    m, l, acc = _decode_partial(q, k, v, kv_pos, length, window)
    return (acc / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)


def decode_attend_partitioned(q, k, v, length, mesh, *, window=None,
                              seq_axis="model", batch_axes=("pod", "data")):
    """Partitioned-KV FPP decode.

    q: [B,H,hd] (replicated over seq_axis); k,v: [B,S,Hkv,hd] with S sharded
    over ``seq_axis`` and B over ``batch_axes``; length: [B].
    """

    S = k.shape[1]
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    nshards = mesh.devices.shape[mesh.axis_names.index(seq_axis)]
    s_loc = S // nshards

    def local(q, k, v, length):
        idx = jax.lax.axis_index(seq_axis)
        kv_pos = idx * s_loc + jnp.arange(s_loc)
        m, l, acc = _decode_partial(q, k, v, kv_pos, length, window)
        return combine_partials(m, l, acc, seq_axis).astype(q.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, None), P(bspec, seq_axis, None, None),
                  P(bspec, seq_axis, None, None), P(bspec)),
        out_specs=P(bspec, None, None), check_vma=False)(q, k, v, length)


# ---------------------------------------------------------------------------
# KV cache


class KVCache(NamedTuple):
    """Per-layer-stacked cache.  k,v: [L, B, S, Hkv, hd]; length: [B]."""
    k: jax.Array
    v: jax.Array
    length: jax.Array

    @staticmethod
    def init(n_layers, batch, max_len, n_kv, head_dim, dtype,
             length: Optional[jax.Array] = None):
        shape = (n_layers, batch, max_len, n_kv, head_dim)
        return KVCache(
            k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
            length=(length if length is not None
                    else jnp.zeros((batch,), jnp.int32)))

    @staticmethod
    def specs(n_layers, batch, max_len, n_kv, head_dim, dtype):
        s = jax.ShapeDtypeStruct((n_layers, batch, max_len, n_kv, head_dim),
                                 dtype)
        return KVCache(k=s, v=s,
                       length=jax.ShapeDtypeStruct((batch,), jnp.int32))


def cache_update_local(k_cache, v_cache, k_new, v_new, length):
    """Write one token at position ``length`` (per sequence) — unsharded.

    k_cache: [B,S,Hkv,hd]; k_new: [B,1,Hkv,hd]; length: [B].
    """
    S = k_cache.shape[1]
    onehot = (jnp.arange(S)[None, :] == length[:, None])  # [B,S]
    oh = onehot[..., None, None].astype(k_cache.dtype)
    k_cache = k_cache * (1 - oh) + k_new * oh
    v_cache = v_cache * (1 - oh) + v_new * oh
    return k_cache, v_cache
