"""Distributed LM runtime on a multi-device host mesh (subprocess: the
8-device XLA flag must precede jax init; the main test process keeps 1)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import _shard_map as shard_map
    from jax.sharding import AxisType

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    # ---- 1. compressed_psum == f32 psum within quantization tolerance ----
    from repro.train.compress import compressed_psum
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)),
                    jnp.float32)
    def f(x):
        return compressed_psum(x, "model")
    got = shard_map(f, mesh=mesh, in_specs=P(None, "model"),
                    out_specs=P(None, "model"))(x)
    def g(x):
        return jax.lax.psum(x, "model")
    want = shard_map(g, mesh=mesh, in_specs=P(None, "model"),
                     out_specs=P(None, "model"))(x)
    err = float(jnp.max(jnp.abs(got - want)))
    rel = err / float(jnp.max(jnp.abs(want)))
    assert rel < 0.02, f"compressed psum rel err {rel}"
    print("compressed_psum ok", rel)

    # ---- 2. sharded train step == single-device train step --------------
    from repro.configs.base import get_config
    from repro.configs.shapes import ShapeConfig
    from repro.models.factory import build_model, input_specs
    from repro.launch.steps import rules_for, build_train_setup
    from repro.train.optimizer import AdamW, constant
    from repro.train.train_step import (init_train_state, make_train_step,
                                        state_shardings, batch_shardings)
    from repro.train.data import batch_for_step

    cfg = get_config("qwen2-72b").reduced()
    shape = ShapeConfig("t", "train", 32, 4)
    model = build_model(cfg)
    opt = AdamW()
    state = init_train_state(model, jax.random.PRNGKey(0), opt)
    batch = batch_for_step(cfg, shape, 0)

    # same microbatch count: the mb-averaged CE metric (mean of per-mb
    # ratios) differs from the single-batch ratio-of-sums when doc-length
    # masks are uneven across microbatches
    plain = jax.jit(make_train_step(model, opt, constant(1e-3),
                                    microbatches=2))
    s1, m1 = plain(state, batch)

    rules = rules_for(cfg, mesh)
    box = {}
    def finit(k):
        p, a = model.init(k)
        box["axes"] = a
        return p
    jax.eval_shape(finit, jax.random.PRNGKey(0))
    st_sh = state_shardings(state, box["axes"], rules)
    b_sh = batch_shardings({k: v for k, v in batch.items()}, rules)
    sharded = jax.jit(make_train_step(model, opt, constant(1e-3),
                                      rules=rules, microbatches=2),
                      in_shardings=(st_sh, b_sh))
    with jax.set_mesh(mesh):
        s2, m2 = sharded(state, batch)
    # microbatched grad averaging reorders float sums: tolerance not exact
    d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(s1.params),
                            jax.tree.leaves(s2.params)))
    print("sharded-vs-plain param delta:", d)
    assert d < 5e-3, d
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-3

    # ---- 3. partitioned-KV decode == local decode ------------------------
    from repro.models import attention as A
    rng = np.random.default_rng(1)
    B, S, H, Hkv, hd = 4, 32, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, hd)), jnp.float32)
    length = jnp.asarray([5, 17, 32, 9], jnp.int32)
    want = A.decode_attend_local(q, k, v, jnp.arange(S), length)
    with jax.set_mesh(mesh):
        got = A.decode_attend_partitioned(q, k, v, length, mesh,
                                          batch_axes=("data",))
    err = float(jnp.max(jnp.abs(got - want)))
    print("partitioned decode err:", err)
    assert err < 1e-5
    print("ALL OK")
""")


@pytest.mark.slow
def test_distributed_train_and_decode():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ALL OK" in out.stdout
