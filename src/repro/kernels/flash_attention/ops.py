"""Jitted wrapper: GQA folding + padding + CPU/TPU dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.contract import KernelContract, TileSpec
from repro.kernels.flash_attention.flash import (DEFAULT_KV_CHUNK,
                                                 DEFAULT_Q_TILE,
                                                 flash_attention_pallas_call)
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.tpu import on_tpu

#: static contract (DESIGN.md §7): canonical bh=8, Sq=Skv=256, hd=64
#: (a reduced-config prefill).  Not reachable from a dispatch table on
#: CPU — models/attention.attend is the XLA twin serving the reduced LM
#:  configs; this kernel is the TPU-native path.  No graph (B, Q), so the
#: planner-model check does not apply; footprint is bounded by VMEM only.
CONTRACTS = (
    KernelContract(
        name="flash_attention",
        module="repro.kernels.flash_attention.flash",
        grid=(8, 2),
        in_tiles=(TileSpec("q", (8, 256, 64), (None, 128, 64)),
                  TileSpec("k", (8, 256, 64), (None, 256, 64)),
                  TileSpec("v", (8, 256, 64), (None, 256, 64))),
        out_tiles=(TileSpec("o", (8, 256, 64), (None, 128, 64)),),
        wired=False,
        note="models/attention.attend is the XLA twin; this kernel is "
             "the TPU-native path for the same blocked online softmax"),
)


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def flash_attention(q, k, v, *, causal=True, window=None,
                    interpret=None):
    """q: [B,Sq,H,hd]; k,v: [B,Skv,Hkv,hd] -> [B,Sq,H,hd].

    GQA: the group dim folds into batch*kv_heads; each program sees the
    q-rows of one kv-head's group against that head's KV.
    """
    if interpret is None:
        interpret = not on_tpu()
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    # [B,Hkv,g,Sq,hd] -> [B*Hkv, g*Sq, hd]: within a row-block, q rows of
    # the same kv-head share that head's KV
    qf = (q.transpose(0, 2, 1, 3).reshape(B, Hkv, g, Sq, hd)
          .reshape(B * Hkv, g * Sq, hd))
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, hd)
    if g > 1:
        # causal positions must not leak across the folded group dim, so
        # run the kernel per group slice instead
        outs = []
        for gi in range(g):
            outs.append(_run(qf.reshape(B * Hkv, g, Sq, hd)[:, gi],
                             kf, vf, causal, window, interpret))
        of = jnp.stack(outs, axis=1)                  # [B*Hkv, g, Sq, hd]
    else:
        of = _run(qf, kf, vf, causal, window, interpret)[:, None]
    out = of.reshape(B, Hkv, g, Sq, hd).reshape(B, H, Sq, hd)
    return out.transpose(0, 2, 1, 3)


def _run(qf, kf, vf, causal, window, interpret):
    sq0 = qf.shape[1]
    qf, _ = _pad_to(qf, 1, DEFAULT_Q_TILE)
    kf, skv0 = _pad_to(kf, 1, DEFAULT_KV_CHUNK)
    vf, _ = _pad_to(vf, 1, DEFAULT_KV_CHUNK)
    out = flash_attention_pallas_call(qf, kf, vf, causal=causal,
                                      window=window, interpret=interpret,
                                      kv_len=skv0)
    return out[:, :sq0]
