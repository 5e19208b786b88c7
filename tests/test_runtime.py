"""Entry-point set-up: where the compile cache lives, and the refusal to
run on the CPU when a TPU was expected."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import runtime

ROOT = Path(__file__).resolve().parents[1]

# compiles one small program with the cache on and every compile kept
_COMPILE = """
import sys
from pathlib import Path
import jax, jax.numpy as jnp
from repro.launch import runtime
if len(sys.argv) > 1:
    runtime.DEFAULT_CACHE_DIR = Path(sys.argv[1])
print(runtime.enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _compile_in_child(env_dir, default_dir):
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _COMPILE, str(default_dir)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def _entries(d: Path):
    return list(d.iterdir()) if d.exists() else []


def test_cache_goes_where_the_env_says(tmp_path):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    assert _compile_in_child(env_dir, default_dir) == str(env_dir)
    assert _entries(env_dir) and not _entries(default_dir)


def test_cache_defaults_to_fixed_dir(tmp_path):
    default_dir = tmp_path / "default"
    assert _compile_in_child(None, default_dir) == str(default_dir)
    assert _entries(default_dir)


def test_default_cache_dir_is_in_the_checkout():
    assert runtime.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"


@pytest.mark.parametrize("platforms,expected", [
    ("cpu", False), ("tpu", True), ("cpu,tpu", True), ("", None)])
def test_tpu_expected(monkeypatch, platforms, expected):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    if expected is None:        # unset: a TPU is expected iff libtpu is
        import importlib.util
        expected = importlib.util.find_spec("libtpu") is not None
    assert runtime.tpu_expected() is expected


def test_check_platform(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert runtime.check_platform().platform == jax.devices()[0].platform
    if jax.devices()[0].platform != "tpu":
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        with pytest.raises(RuntimeError, match="expected a TPU"):
            runtime.check_platform()
