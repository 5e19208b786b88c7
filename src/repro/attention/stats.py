"""One normalized stats shape for every attention backend.

The pre-registry code emitted three different stats shapes (a frozen
``HDPStats`` dataclass from ``core.hdp``, ad-hoc dicts from the model
paths, another dict from the kernel pipeline). Every registered backend
now returns ``AttnStats | None`` — a registered JAX pytree, so it rides
through ``jax.jit`` / ``lax.scan`` (the per-layer stack in
``transformer._stack``) unchanged. Dict-style access is kept so existing
consumers (``benchmarks/common.py``, examples) keep working.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class AttnStats:
    """Diagnostics from one attention call (all jnp arrays or None).

    block_sparsity: scalar pruned-block fraction over valid blocks.
    head_sparsity: scalar pruned-head fraction.
    theta_head: per-head importances [..., heads-shaped] (optional).
    page_sparsity: scalar never-fetched page fraction (paged decode only).
    kernel_pages: kept pages the Pallas paged decode kernel visits, per
        row (that kernel only).
    kernel_block_pages: pages of the compute blocks it visits, per row:
        ``ceil(kernel_pages / ppb) * ppb``.
    scout_pages: table columns the Pallas paged scout kernel walks (the
        pages it DMAs), per row (that kernel only).
    """

    block_sparsity: jnp.ndarray
    head_sparsity: jnp.ndarray
    theta_head: Optional[jnp.ndarray] = None
    page_sparsity: Optional[jnp.ndarray] = None
    kernel_pages: Optional[jnp.ndarray] = None
    kernel_block_pages: Optional[jnp.ndarray] = None
    scout_pages: Optional[jnp.ndarray] = None

    # dict-style compat with the pre-registry stats consumers
    def __getitem__(self, key: str):
        val = getattr(self, key)
        if val is None:
            raise KeyError(key)
        return val

    def get(self, key: str, default=None):
        try:
            return self[key]
        except (KeyError, AttributeError):
            return default

    def __contains__(self, key: str) -> bool:
        return self.get(key) is not None


jax.tree_util.register_dataclass(
    AttnStats,
    data_fields=("block_sparsity", "head_sparsity", "theta_head",
                 "page_sparsity", "kernel_pages", "kernel_block_pages",
                 "scout_pages"),
    meta_fields=())


def normalize_stats(raw: Any) -> Optional[AttnStats]:
    """Coerce a backend's native stats (dict / HDPStats / None) to AttnStats."""
    if raw is None or isinstance(raw, AttnStats):
        return raw
    if isinstance(raw, Mapping):
        return AttnStats(
            block_sparsity=jnp.asarray(raw["block_sparsity"]),
            head_sparsity=jnp.asarray(raw["head_sparsity"]),
            theta_head=raw.get("theta_head"),
            page_sparsity=raw.get("page_sparsity"),
            kernel_pages=raw.get("kernel_pages"),
            kernel_block_pages=raw.get("kernel_block_pages"),
            scout_pages=raw.get("scout_pages"))
    # core.hdp.HDPStats-shaped object (attribute access)
    return AttnStats(
        block_sparsity=jnp.asarray(raw.block_sparsity),
        head_sparsity=jnp.asarray(raw.head_sparsity),
        theta_head=getattr(raw, "theta_head", None),
        page_sparsity=getattr(raw, "page_sparsity", None))
