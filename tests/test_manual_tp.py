"""Manual (shard_map) TP blocks == auto-GSPMD forward, bit-for-bit-ish.

Subprocess: needs 8 fake devices before jax init.
"""
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
    from jax.sharding import AxisType
    from repro.configs.base import get_config
    from repro.models.factory import build_model
    from repro.launch.steps import rules_for
    from repro.models import manual_tp

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    for arch in ("qwen2-72b", "stablelm-12b"):
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)), jnp.int32)
        batch = {"tokens": tokens}

        rules = rules_for(cfg, mesh)
        assert manual_tp.mlp_eligible(cfg, rules), (arch, cfg.d_ff)
        assert manual_tp.attn_eligible(cfg, rules), (
            arch, cfg.n_heads, cfg.n_kv_heads)

        base, _ = model.logits(params, batch, remat=False)   # no rules

        rules.rules["manual_tp"] = True
        with jax.set_mesh(mesh):
            got, _ = jax.jit(lambda p, b: model.logits(
                p, b, rules=rules, remat=False))(params, batch)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - base.astype(jnp.float32))))
        print(arch, "manual-vs-auto max err:", err)
        # bf16 psum-reorder noise; with compute_dtype=float32 the same
        # comparison lands at 1.8e-6 (verified during bring-up)
        assert err < 6e-2, (arch, err)
    print("ALL OK")
""")


@pytest.mark.slow
def test_manual_tp_matches_auto():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "ALL OK" in out.stdout
