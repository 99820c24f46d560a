"""chip_smoke.py rehearsed on the CPU at a tiny size.

The script is the quickest proof that the system still starts on the
chip, so its control flow and oracle checks are kept covered here: the
one-chip phases run in-process on grid2d(24, 24) with the TPU check
stubbed by the test, the four-chip phase on four forced host devices, and
the unstubbed script must refuse to run without a TPU.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
SCRIPT = REPO / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_rehearse_on_cpu(monkeypatch, capsys, tmp_path):
    import jax

    import repro.launch.persistent_cache as pc
    smoke = _load()
    monkeypatch.setattr(smoke, "require_tpu", lambda chips: jax.devices())
    monkeypatch.setattr(pc, "enable_persistent_cache", lambda: tmp_path)
    monkeypatch.setattr(smoke, "ROWS", 24)
    monkeypatch.setattr(smoke, "NUM_QUERIES", 8)
    assert smoke.main([]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": 1}}
    checks = [ln for ln in out if ln.startswith("[check]")]
    assert len(checks) == 8 and all("ok" in ln for ln in checks)
    assert any("576 vertices, 2,208 directed edges" in ln for ln in out)


def test_failed_check_exits_nonzero(monkeypatch, capsys, tmp_path):
    import jax

    import repro.launch.persistent_cache as pc
    smoke = _load()
    monkeypatch.setattr(smoke, "require_tpu", lambda chips: jax.devices())
    monkeypatch.setattr(pc, "enable_persistent_cache", lambda: tmp_path)

    def broken(*size):
        smoke.check(False, "a deliberately failed check")

    monkeypatch.setattr(smoke, "one_chip", broken)
    assert smoke.main([]) == 1
    assert '"ok"' not in capsys.readouterr().out


_FOUR = """
import importlib.util, json, sys
import jax
import repro.launch.persistent_cache as pc
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke.require_tpu = lambda chips: jax.devices()
pc.enable_persistent_cache = lambda: None
smoke.ROWS, smoke.NUM_QUERIES = 24, 8
sys.exit(smoke.main(["--chips", "4"]))
"""


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.update(extra)
    return env


def test_four_chip_phase_rehearses_on_host_devices():
    out = subprocess.run(
        [sys.executable, "-c", _FOUR, str(SCRIPT)], capture_output=True,
        text=True, timeout=600, env=_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1])["device"]["count"] == 4
    assert sum("[check] ok" in ln for ln in lines) == 2


def test_script_refuses_to_run_without_a_tpu(tmp_path):
    out = subprocess.run(
        [sys.executable, str(SCRIPT)], capture_output=True, text=True,
        timeout=300, cwd=tmp_path,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")))
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout
