"""Shared layers for the LM zoo: norms, RoPE, embeddings, (gated) MLP.

Every ``init_*`` returns ``(params, axes)`` — two pytrees with identical
structure, where each leaf of ``axes`` is a tuple of logical axis names
(see models/sharding.py).  Model code stays sharding-agnostic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# init helpers


def _normal(key, shape, dtype, scale):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def dense_init(key, in_dim, out_shape, dtype, axes):
    """Fan-in scaled init for a [in_dim, *out_shape] weight."""
    shape = (in_dim,) + tuple(out_shape)
    return _normal(key, shape, dtype, 1.0 / np.sqrt(in_dim)), axes


# ---------------------------------------------------------------------------
# norms


def init_norm(dtype, d, kind="rmsnorm"):
    p = {"scale": jnp.ones((d,), dtype)}
    a = {"scale": ("embed",)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
        a["bias"] = ("embed",)
    return p, a


def apply_norm(p, x, kind="rmsnorm", eps=1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (n * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    n = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = n * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                        # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(ang)[..., None, :]                     # [..., S, 1, hd/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# embedding / unembedding


def init_embedding(key, vocab, d, dtype, tie=False):
    k1, k2 = jax.random.split(key)
    p = {"embedding": _normal(k1, (vocab, d), dtype, 1.0)}
    # rows sharded over "model", D replicated: a row-sharded table gathers
    # with local masking + one small all-reduce; a 2-D-sharded table forces
    # GSPMD into involuntary full rematerialization of the gather.
    a = {"embedding": ("vocab", "vocab_embed")}
    if not tie:
        p["unembed"] = _normal(k2, (d, vocab), dtype, 1.0 / np.sqrt(d))
        a["unembed"] = ("embed", "vocab")
    return p, a


def embed(p, tokens, cdtype, rules=None):
    """Token embedding lookup.

    With a vocab-sharded table and a mesh in scope, the lookup runs as an
    explicit shard_map: each shard gathers the rows it owns (local ids,
    masked) and one psum over "model" combines.  GSPMD's generic handling
    of a cross-shard gather is involuntary full rematerialization — it
    replicates the f32 table per microbatch (measured: +12 GB/device on
    mistral-large; EXPERIMENTS.md §Perf).
    """
    table = p["embedding"]
    if rules is not None:
        ax = rules.rules.get("vocab")
        mesh = rules.mesh
        if ax in mesh.axis_names and mesh.devices.shape[
                mesh.axis_names.index(ax)] > 1 \
                and table.shape[0] % rules._mesh_size(ax) == 0:
            return _sharded_embed(table, tokens, rules, ax, cdtype)
    return table.astype(cdtype)[tokens]


def _sharded_embed(table, tokens, rules, ax, cdtype):
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    n = rules._mesh_size(ax)
    v_loc = table.shape[0] // n
    bspec = rules.rules["batch"]
    if tokens.shape[0] % max(rules._mesh_size(bspec), 1):
        bspec = None    # tiny batches (long_500k: B=1) stay replicated

    def local(tab, tok):
        idx = jax.lax.axis_index(ax)
        loc = tok - idx * v_loc
        ok = (loc >= 0) & (loc < v_loc)
        x = tab.astype(cdtype)[jnp.clip(loc, 0, v_loc - 1)]
        x = x * ok[..., None].astype(cdtype)
        return jax.lax.psum(x, ax)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(ax, None), P(bspec, None)),
                         out_specs=P(bspec, None, None),
                         check_vma=False)(table, tokens)


def unembed(p, x, true_vocab=None):
    w = p.get("unembed")
    if w is None:
        w = p["embedding"].T
    logits = jnp.einsum("...d,dv->...v", x, w.astype(x.dtype))
    if true_vocab is not None and true_vocab < logits.shape[-1]:
        # padded vocab rows can never win or receive gradient mass
        neg = jnp.asarray(-1e9, logits.dtype)
        mask = jnp.arange(logits.shape[-1]) < true_vocab
        logits = jnp.where(mask, logits, neg)
    return logits


# ---------------------------------------------------------------------------
# MLP (SwiGLU-gated or plain)


def init_mlp(key, d, d_ff, dtype, gated=True):
    ks = jax.random.split(key, 3)
    s_in, s_out = 1.0 / np.sqrt(d), 1.0 / np.sqrt(d_ff)
    if gated:
        p = {"wi": _normal(ks[0], (d, d_ff), dtype, s_in),
             "wg": _normal(ks[1], (d, d_ff), dtype, s_in),
             "wo": _normal(ks[2], (d_ff, d), dtype, s_out)}
        a = {"wi": ("embed", "mlp"), "wg": ("embed", "mlp"),
             "wo": ("mlp", "embed")}
    else:
        p = {"wi": _normal(ks[0], (d, d_ff), dtype, s_in),
             "wo": _normal(ks[2], (d_ff, d), dtype, s_out)}
        a = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
    return p, a


def _act(x, act):
    return jax.nn.silu(x) if act == "silu" else jax.nn.gelu(x)


def apply_mlp(p, x, act="silu"):
    h = jnp.einsum("...d,df->...f", x, p["wi"].astype(x.dtype))
    h = _act(h, act)
    if "wg" in p:
        h = h * jnp.einsum("...d,df->...f", x, p["wg"].astype(x.dtype))
    return jnp.einsum("...f,fd->...d", h, p["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# misc


def pad_vocab(vocab: int, multiple: int = 256) -> int:
    return int(-(-vocab // multiple) * multiple)


def stack_layers(leaves: list):
    """Stack per-layer param pytrees into a single scanned pytree."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *leaves)


def add_layer_axis(axes_tree):
    """Prefix each logical-axes tuple with the scanned 'layers' axis."""
    return jax.tree.map(
        lambda ax: ("layers",) + ax, axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and
        all(isinstance(e, (str, type(None))) for e in x))
