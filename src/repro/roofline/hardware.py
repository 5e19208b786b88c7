"""Pluggable hardware profiles for roofline and cost-model predictions.

The dry-run roofline tables always projected TPU v5e numbers from
module-level constants in ``analysis.py``; the serving autotuner
(``repro.autotune``) reuses the same constants to predict attention-
backend step times — but it runs wherever the engine runs, which in CI
and on dev machines is a CPU host. A cost prediction made with TPU
bandwidth on a CPU host is silently wrong in a way that flips backend
choices, so the constants live here as named profiles and
:func:`detect_profile` picks the one matching the actual JAX backend.

``analysis.py`` keeps re-exporting the TPU v5e numbers under their old
names (``PEAK_FLOPS`` / ``HBM_BW`` / ``ICI_BW`` / ``HBM_BYTES``): the
dry-run tables intentionally project the deploy target, not the host.

A device whose ``device_kind`` has no profile is an error, never a
silent default: peaks of one chip generation would misprice another.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    """Per-chip performance envelope + dispatch-cost constants.

    Attributes:
      peak_flops: dense matmul peak (bf16 for TPU profiles).
      hbm_bw: main-memory bandwidth in bytes/s.
      ici_bw: interconnect bandwidth in bytes/s per link.
      mem_bytes: main-memory capacity.
      dispatch_s: fixed per-jitted-call overhead (host dispatch + launch).
      op_overhead_s: per fused-op overhead inside one call — the term
        that makes multi-stage sparse pipelines lose to one dense matmul
        at short kv_len.
      pallas_native: Pallas kernels compile natively; when False they run
        in interpret mode and predictions scale by ``interpret_slowdown``
        so auto-selection can never cost-pick an interpreted kernel.
    """

    name: str
    peak_flops: float
    hbm_bw: float
    ici_bw: float
    mem_bytes: float
    dispatch_s: float = 5e-6
    op_overhead_s: float = 1e-6
    pallas_native: bool = False
    interpret_slowdown: float = 1.0


#: TPU v5e, per chip (the numbers analysis.py always used).
TPU_V5E = HardwareProfile(
    name="tpu_v5e", peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
    mem_bytes=16 * 2 ** 30, dispatch_s=5e-6, op_overhead_s=2e-7,
    pallas_native=True, interpret_slowdown=1.0)

#: Conservative CPU host envelope (CI runners, dev machines): XLA:CPU
#: matmul throughput and DRAM bandwidth, with Pallas in interpret mode.
#: Absolute numbers are order-of-magnitude — the autotuner compares
#: backends under ONE profile, so ranking needs the ratios right
#: (sparsity x kv_len vs per-op overhead), not the absolutes.
HOST_CPU = HardwareProfile(
    name="host_cpu", peak_flops=5e10, hbm_bw=2.5e10, ici_bw=1e9,
    mem_bytes=8 * 2 ** 30, dispatch_s=2e-5, op_overhead_s=2e-6,
    pallas_native=False, interpret_slowdown=500.0)

PROFILES = {p.name: p for p in (TPU_V5E, HOST_CPU)}

#: ``jax.Device.device_kind`` -> profile, for every accelerator whose
#: published peaks are above (Google Cloud documentation, "TPU v5e").
DEVICE_KINDS = {"TPU v5 lite": TPU_V5E}


def get_profile(name: str) -> HardwareProfile:
    try:
        return PROFILES[name]
    except KeyError:
        raise KeyError(f"unknown hardware profile {name!r}; "
                       f"have {sorted(PROFILES)}") from None


def detect_profile() -> HardwareProfile:
    """Profile of the device JAX actually runs on, keyed on its
    ``device_kind``; the CPU host profile for CPU devices. An accelerator
    with no profile raises."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return HOST_CPU
    try:
        return DEVICE_KINDS[dev.device_kind]
    except KeyError:
        raise RuntimeError(
            f"no hardware profile for {dev.platform} device_kind "
            f"{dev.device_kind!r}; known: {sorted(DEVICE_KINDS)}") from None
