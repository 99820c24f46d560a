"""Compile the main path's kernels and programs for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, installed beside JAX,
compiles for a ``v5e:2x2`` topology that is described, not attached.  It
refuses what interpret mode accepts — unaligned slices, more VMEM than a
kernel may use, a program that does not fit HBM — so these tests guard
every kernel of the main path at the smoke run's shapes (``chip_smoke.py``:
grid2d(512, 512), B = 1024, Q = 64) without chip time.

Every chip-compiler test of the repo lives in this one file: the topology
is described inside a module fixture (never at import), so only the
worker that runs this file loads the TPU library.
"""
import os
import unittest.mock as mock

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

#: the smoke run's plan: grid2d(512, 512) on v5e -> B = 1024, P = 256,
#: 766 dense blocks; dmax at the planner's fused neighbor-slot budget
B, P, NBLK, Q = 1024, 256, 766, 64
DMAX = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        desc, reason = None, str(e)
    if desc is not None:
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    if desc is None:
        pytest.skip(f"no v5e:2x2 topology can be described here: {reason}")


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _compiled_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_minplus_kernel_compiles(shape):
    from repro.kernels.minplus.minplus import minplus_pallas_call
    _compiled_kernel(lambda d, w: minplus_pallas_call(d, w, interpret=False),
                     shape((Q, B)), shape((B, B)))


def test_masked_matmul_kernel_compiles(shape):
    from repro.kernels.minplus.minplus import masked_matmul_pallas_call
    _compiled_kernel(lambda x, w: masked_matmul_pallas_call(
        x, w, interpret=False), shape((Q, B)), shape((B, B)))


def test_ppr_push_kernel_compiles(shape):
    from repro.kernels.ppr_push.push import ppr_push_pallas_call
    tile = shape((Q, B))
    _compiled_kernel(lambda p, r, a, w, g: ppr_push_pallas_call(
        p, r, a, w, g, alpha=0.15, eps=1e-4, interpret=False),
        tile, tile, tile, shape((B, B)), shape((1, B)))


def test_frontier_kernel_compiles(shape):
    from repro.kernels.frontier.frontier import frontier_pallas_call
    _compiled_kernel(lambda b, d: frontier_pallas_call(
        b, d, delta=1.0, interpret=False), shape((Q, B)), shape((Q, B)))


def _device_graph(shape, dmax=DMAX, p=P, b=B, nblk=NBLK):
    """(stand-in graph for building, graph of shapes for lowering)."""
    from repro.core.engine import DeviceGraph
    i32 = jnp.int32
    meta = DeviceGraph(
        blocks=None, row_nnz=None, nbr_blk=None,
        nbr_part=np.full((p, dmax), -1, np.int32), diag_blk=None, deg=None,
        vmask=None, edge_budget=None, num_parts=p, block_size=b, dmax=dmax)
    shapes = DeviceGraph(
        blocks=shape((nblk, b, b)), row_nnz=shape((nblk, b), i32),
        nbr_blk=shape((p, dmax), i32), nbr_part=shape((p, dmax), i32),
        diag_blk=shape((p,), i32), deg=shape((p, b), i32),
        vmask=shape((p, b), jnp.bool_), edge_budget=shape((p,)),
        num_parts=p, block_size=b, dmax=dmax)
    return meta, shapes


def _algebra(kind):
    from repro.core.visit import minplus_algebra, push_algebra
    return (minplus_algebra(1.0) if kind == "minplus"
            else push_algebra(0.15, 1e-4))


@pytest.mark.parametrize("kind,mode,p,b,nblk", [
    ("minplus", "dense", P, B, NBLK),
    ("minplus", "sparse", P, B, NBLK),
    ("push", "dense", P, B, NBLK),
    # many small partitions: the kernel's SMEM holds one scheduler entry
    # per grid slot, never a [P+1] table (7 of them would need 1.8 MiB of
    # v5e's 1 MiB SMEM here), so P is not bounded by SMEM
    ("minplus", "dense", 65536, 128, 65536),
])
def test_fused_visit_kernel_compiles(shape, kind, mode, p, b, nblk):
    from repro.kernels.frontier.ops import frontier_tile
    from repro.kernels.fused_visit.fused import PackedState
    from repro.kernels.fused_visit.ops import make_fused_visit
    from repro.kernels.ppr_push.ops import push_tile
    meta, graph = _device_graph(shape, p=p, b=b, nblk=nblk)
    alg = _algebra(kind)
    fv = make_fused_visit(meta, alg, 64, frontier=frontier_tile,
                          push=push_tile, frontier_mode=mode,
                          interpret=False)
    c = alg.num_planes + 1
    packed = PackedState(shape((p + 1, c, Q, b)), shape((p + 1,)),
                         shape((p + 1,), jnp.int32),
                         shape((p + 1,), jnp.int32))
    _compiled_kernel(lambda g, pk, v, n: fv.visit(fv.graph(g), pk, v, n),
                     graph, packed, shape((), jnp.int32),
                     shape((), jnp.int32))


@pytest.mark.parametrize("fused", [False, True])
def test_megastep_compiles_without_copying_the_block_store(topo, shape,
                                                           fused):
    """The engine's K-visit megastep at the smoke shapes, built as the
    engine builds it on a TPU backend: the fused body lowers to the Mosaic
    kernel (never interpret mode), and neither body copies the block
    store (a gather over it used to make XLA split the whole store inside
    the visit loop)."""
    from repro.core import visit as _visit
    meta, graph = _device_graph(shape, dmax=6)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(jax, "devices", lambda *a: topo.devices):
        ms = _visit.make_megastep(meta, _algebra("minplus"), B, K=64,
                                  fused=fused, harvest_mask=True)
    state = _visit.VisitState((shape((P, Q, B)),), shape((P + 1, Q, B)),
                              shape((P,)), shape((P,), jnp.int32),
                              shape((P,), jnp.int32))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    compiled = ms.fn.lower(graph, state, shape((), jnp.int32),
                           shape((), jnp.int32),
                           shape(key.shape, key.dtype)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == fused
    block_store = NBLK * B * B * 4
    assert compiled.memory_analysis().temp_size_in_bytes < block_store // 8


@pytest.mark.parametrize("kind", ["sssp", "ppr"])
def test_distributed_program_compiles_for_four_chips(topo, kind):
    """The mesh program over the four described chips, its slabs placed
    with the same NamedShardings ``_run_program`` uses: each chip holds a
    quarter of the block store, and the exchange is an all-to-all."""
    from jax.sharding import Mesh, NamedSharding
    from repro.core import distributed as D

    class Shape:
        block_size, num_parts = B, P
        nbr_blk = np.zeros((P, 6), np.int32)

    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    fn, args = D.make_distributed_program(Shape, Q, mesh, kind=kind)
    specs = D._program_specs(("data",), "model")
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                 sharding=NamedSharding(mesh, s))
            for a, s in zip(args, specs)]
    compiled = fn.lower(*args).compile()
    assert "all-to-all" in compiled.as_text()
    slab = P * 7 * B * B * 4
    assert compiled.memory_analysis().argument_size_in_bytes < slab // 3
