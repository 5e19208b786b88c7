"""Process set-up shared by the entry points (serve CLI, benchmark
harness, ``chip_smoke.py``): the persistent compilation cache and the
check that JAX came up on the platform the run expects.

Nothing here runs at import; each entry point calls these first thing in
its ``main``, before anything compiles.
"""
from __future__ import annotations

import importlib.util
import os
from pathlib import Path

import jax

#: Fixed in-checkout cache directory used when the environment names
#: none (a stable path: the directory is part of every entry's key).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it
    and no other directory is set; otherwise the cache lives in
    :data:`DEFAULT_CACHE_DIR`. Every compile of a second or more is kept,
    so a repeat run of the same shapes skips XLA compilation.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def tpu_expected() -> bool:
    """A TPU run is expected unless ``JAX_PLATFORMS`` pins other
    platforms: either it names ``tpu``, or it is unset on a machine with
    the TPU runtime (libtpu) installed."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms:
        return "tpu" in platforms.split(",")
    return importlib.util.find_spec("libtpu") is not None


def check_platform() -> jax.Device:
    """First device JAX runs on; raises when a TPU was expected and JAX
    came up on another platform (for instance the chip was held by
    another process and JAX fell back to the CPU)."""
    dev = jax.devices()[0]
    if tpu_expected() and dev.platform != "tpu":
        raise RuntimeError(
            f"expected a TPU but JAX runs on {dev.platform} "
            f"({dev.device_kind}); set JAX_PLATFORMS=cpu to run on the CPU "
            "on purpose")
    return dev
