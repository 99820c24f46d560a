"""The persistent compilation cache lives in one fixed, predictable place.

Each case runs in a fresh interpreter: enabling the cache is process-wide
JAX configuration, and JAX reads ``JAX_COMPILATION_CACHE_DIR`` once, when
it is imported.
"""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

_PROBE = (
    "import jax\n"
    "from repro.launch.persistent_cache import enable_persistent_cache\n"
    "print(enable_persistent_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def _probe(env_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    returned, configured = out.stdout.strip().splitlines()[-2:]
    return returned, configured


def test_cache_dir_honours_the_environment(tmp_path):
    returned, configured = _probe(tmp_path / "jaxcache")
    assert returned == configured == str(tmp_path / "jaxcache")


def test_cache_dir_defaults_to_the_checkout():
    returned, configured = _probe(None)
    assert returned == configured == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
