"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis carries
only data-parallel gradient reduction (hierarchical: reduce-scatter inside
a pod over `data`, then cross-pod all-reduce over `pod` — DCN-friendly).

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices) -> jax.sharding.Mesh:
    """Mesh whose axes are all ``Auto``: the serving and training code
    places arrays with ``NamedSharding`` + ``with_sharding_constraint``
    and lets the partitioner propagate, which JAX's default ``Explicit``
    axis types refuse outside a ``jax.set_mesh`` context."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devs)} — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={need} before any jax import")
    return _auto_mesh(shape, axes, devs[:need])


def make_serving_mesh(tp: int = 1, dp: int = 1,
                      devices=None) -> jax.sharding.Mesh:
    """Small serving mesh: (data=dp, model=tp) over whatever devices exist.

    Unlike `make_production_mesh` this builds from the devices actually
    present (host-CPU friendly: set
    XLA_FLAGS=--xla_force_host_platform_device_count=N before first jax
    init to fake N devices). `tp` shards KV-head/pool state, `dp` is the
    engine-replica axis.
    """
    tp, dp = int(tp), int(dp)
    if tp < 1 or dp < 1:
        raise ValueError(f"make_serving_mesh: tp={tp} dp={dp} must be >= 1")
    devs = list(jax.devices()) if devices is None else list(devices)
    need = tp * dp
    if len(devs) < need:
        raise RuntimeError(
            f"serving mesh (dp={dp}, tp={tp}) needs {need} devices, have "
            f"{len(devs)} — on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            "before any jax import")
    return _auto_mesh((dp, tp), ("data", "model"), devs[:need])


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    data = n // model
    return _auto_mesh((data, model), ("data", "model"),
                      jax.devices()[: data * model])
