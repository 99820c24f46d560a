"""Per-kernel shape/dtype sweeps: pallas_call (interpret=True) vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.frontier.ops import frontier_pallas
from repro.kernels.frontier.ref import frontier_ref
from repro.kernels.ppr_push.ops import ppr_push_pallas
from repro.kernels.ppr_push.ref import ppr_push_ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("B,Sq,Skv,H,Hkv,hd,causal,window", [
    (2, 64, 64, 4, 4, 32, True, None),     # MHA causal
    (1, 48, 80, 4, 2, 16, True, None),     # GQA, cross lengths, pad path
    (2, 32, 32, 8, 1, 64, False, None),    # MQA non-causal
    (1, 128, 128, 4, 4, 32, True, 32),     # windowed (recurrentgemma)
    (1, 16, 300, 2, 2, 8, False, None),    # KV padding (1500-frame-like)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Sq, Skv, H, Hkv, hd, causal, window,
                               dtype):
    q = jnp.asarray(RNG.normal(size=(B, Sq, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Skv, Hkv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Skv, Hkv, hd)), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    g = H // Hkv
    kr, vr = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = kr.transpose(0, 2, 1, 3).reshape(B * H, Skv, hd)
    vf = vr.transpose(0, 2, 1, 3).reshape(B * H, Skv, hd)
    want = flash_attention_ref(qf, kf, vf, causal=causal, window=window)
    want = want.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol)


@pytest.mark.parametrize("Q,B", [(8, 32), (17, 64), (128, 128), (1, 16)])
@pytest.mark.parametrize("delta", [0.5, 2.0, np.inf])
def test_frontier_sweep(Q, B, delta):
    buf = jnp.asarray(np.where(RNG.random((Q, B)) < 0.6, np.inf,
                               RNG.random((Q, B)) * 9), jnp.float32)
    dist = jnp.asarray(np.where(RNG.random((Q, B)) < 0.5, np.inf,
                                RNG.random((Q, B)) * 9), jnp.float32)
    got = frontier_pallas(buf, dist, delta=float(delta))
    want = frontier_ref(buf, dist, delta=float(delta))
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            np.nan_to_num(np.asarray(g), posinf=1e30),
            np.nan_to_num(np.asarray(w), posinf=1e30), rtol=1e-6)


@pytest.mark.parametrize("Q,B", [(8, 32), (25, 64), (128, 128)])
@pytest.mark.parametrize("alpha,eps", [(0.15, 1e-4), (0.5, 1e-2)])
def test_ppr_push_sweep(Q, B, alpha, eps):
    p = jnp.asarray(RNG.random((Q, B)), jnp.float32) * 0.05
    r = jnp.asarray(RNG.random((Q, B)), jnp.float32) * 0.02
    acc = jnp.asarray(RNG.random((Q, B)), jnp.float32) * 0.01
    w = jnp.asarray(np.where(RNG.random((B, B)) < 0.85, np.inf,
                             RNG.random((B, B))), jnp.float32)
    deg = jnp.asarray(np.isfinite(np.asarray(w)).sum(1), jnp.float32)
    got = ppr_push_pallas(p, r, acc, w, deg, alpha=alpha, eps=eps)
    want = ppr_push_ref(p, r, acc, w, deg[None], alpha=alpha, eps=eps)
    for g, wv in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wv),
                                   atol=1e-6)


def _relax_call(d, w, **kw):
    """``minplus_relax`` in a one-program kernel of its own, as the fused
    visit calls it (chunk size and ``skip_inactive`` are its options)."""
    from jax.experimental import pallas as pl
    from repro.kernels.minplus.minplus import minplus_relax

    def kernel(d_ref, w_ref, o_ref):
        o_ref[...] = minplus_relax(d_ref, w_ref, **kw)
    return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
        d.shape, d.dtype), interpret=True)(d, w)


@pytest.mark.parametrize("Q,B", [(3, 16), (8, 32), (64, 64)])
@pytest.mark.parametrize("u_chunk", [4, 8, 16])
def test_minplus_tile_skip_inactive_bitwise(Q, B, u_chunk):
    """The fused visit's in-kernel relax: chunked, chunk-skipping, and
    single-chunk paths of ``minplus_relax`` all agree with the ref down to
    the bit — skipping an all-+inf source chunk contributes only +inf
    candidates, and chunking only reassociates an exact min.  The minplus
    kernel itself (dense relax) is held to the same bits."""
    from repro.kernels.minplus.minplus import minplus_pallas_call
    from repro.kernels.minplus.ref import minplus_ref
    d = jnp.asarray(np.where(RNG.random((Q, B)) < 0.7, np.inf,
                             RNG.random((Q, B)) * 9), jnp.float32)
    w = jnp.asarray(np.where(RNG.random((B, B)) < 0.8, np.inf,
                             RNG.random((B, B)) * 5), jnp.float32)
    want = np.nan_to_num(np.asarray(minplus_ref(d, w)), posinf=1e30)
    for kw in ({"u_chunk": u_chunk}, {"u_chunk": u_chunk,
                                      "skip_inactive": True},
               {"u_chunk": B}, {"u_chunk": B, "skip_inactive": True}):
        got = np.nan_to_num(np.asarray(_relax_call(d, w, **kw)),
                            posinf=1e30)
        np.testing.assert_array_equal(got, want, err_msg=str(kw))
    got = minplus_pallas_call(d, w, u_chunk=u_chunk, interpret=True)
    np.testing.assert_array_equal(
        np.nan_to_num(np.asarray(got), posinf=1e30), want)


@pytest.mark.parametrize("Q,B", [(3, 16), (64, 64)])
@pytest.mark.parametrize("delta", [0.5, 2.0, np.inf])
def test_frontier_tile_matches_ref(Q, B, delta):
    """The fused visit's consolidation op: tile == ref on every output,
    including the extra [QT, 1] alpha row the kernel path keeps."""
    from repro.kernels.frontier.frontier import frontier_tile
    from repro.kernels.frontier.ref import frontier_ref
    buf = jnp.asarray(np.where(RNG.random((Q, B)) < 0.6, np.inf,
                               RNG.random((Q, B)) * 9), jnp.float32)
    dist = jnp.asarray(np.where(RNG.random((Q, B)) < 0.5, np.inf,
                                RNG.random((Q, B)) * 9), jnp.float32)
    d1, srcs, alpha, pending, active = frontier_tile(buf, dist,
                                                     delta=float(delta))
    assert alpha.shape == (Q, 1)
    want = frontier_ref(buf, dist, delta=float(delta))
    for g, w in zip((d1, srcs), want[:2]):
        np.testing.assert_array_equal(
            np.nan_to_num(np.asarray(g), posinf=1e30),
            np.nan_to_num(np.asarray(w), posinf=1e30))


@pytest.mark.parametrize("Q,B", [(3, 16), (16, 64)])
def test_push_tile_lane_mask(Q, B):
    """The fused visit's per-query edge-budget gate: an all-true lane mask
    is bitwise the unmasked op; an all-false mask freezes the tile (no
    pushes, p/r/acc unchanged, empty active set)."""
    from repro.kernels.ppr_push.push import push_tile
    p = jnp.asarray(RNG.random((Q, B)), jnp.float32) * 0.05
    r = jnp.asarray(RNG.random((Q, B)), jnp.float32) * 0.02
    acc = jnp.asarray(RNG.random((Q, B)), jnp.float32) * 0.01
    w = jnp.asarray(np.where(RNG.random((B, B)) < 0.85, np.inf,
                             RNG.random((B, B))), jnp.float32)
    deg = jnp.asarray(np.isfinite(np.asarray(w)).sum(1), jnp.float32)
    kw = dict(alpha=0.15, eps=1e-4)
    base = push_tile(p, r, acc, w, deg, **kw)
    ones = push_tile(p, r, acc, w, deg,
                     lane_mask=jnp.ones((Q, B), bool), **kw)
    for g, want in zip(ones, base):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want))
    p1, r1, acc1, active = push_tile(p, r, acc, w, deg,
                                     lane_mask=jnp.zeros((Q, B), bool),
                                     **kw)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r))
    np.testing.assert_array_equal(np.asarray(acc1), np.asarray(acc))
    assert not np.asarray(active).any()


def test_flash_attention_used_as_model_attention():
    """The kernel slots into the model attention contract (same output as
    models/attention.attend)."""
    from repro.models.attention import attend
    B, S, H, Hkv, hd = 2, 32, 4, 2, 16
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)), jnp.float32)
    pos = jnp.arange(S)
    want = attend(q, k, v, pos, pos, causal=True, chunk=8)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)
