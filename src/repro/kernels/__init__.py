"""Pallas TPU kernels for the HDP hot spots + dense baseline.

Compiled natively on TPU; off-TPU (tests with ``JAX_PLATFORMS=cpu``) they
run in Pallas interpret mode.
"""
from repro.kernels.ops import flash, hdp_attention_tpu

__all__ = ["flash", "hdp_attention_tpu"]
