"""Kernel microbenchmark: correctness + FUM memory-traffic accounting.

Kernels run natively on a TPU and in Pallas interpret mode on the CPU
(``JAX_PLATFORMS=cpu``); interpret mode checks results, never speed. The
benchmark verifies (a) allclose vs the pure-jnp oracle across shapes, and (b) the
*structural* memory win of Fetch-Upon-Mask — HBM bytes that the
block-sparse kernel's BlockSpecs fetch vs the dense flash kernel, at the
sparsity level the scout actually produced. On hardware (b) is the
bandwidth saving; the byte accounting below is exact because the grid +
BlockSpec decide DMA traffic deterministically.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import HDPConfig
from repro.core.hdp import hdp_attention
from repro.kernels import ops
from repro.kernels import ref as kref

SHAPES = ((1, 2, 256, 64), (2, 4, 128, 64))


def flash_bytes(B, H, Sq, Sk, hd, bq, bk, itemsize=4) -> int:
    """Dense flash: Q once, K/V once per q-block (no reuse across rows)."""
    nq = -(-Sq // bq)
    q = B * H * Sq * hd
    kv = 2 * B * H * nq * Sk * hd
    o = B * H * Sq * hd
    return (q + kv + o) * itemsize


def fum_bytes(B, H, Sq, Sk, hd, bq, bk, counts, itemsize=4) -> int:
    """FUM: K/V fetched only for kept blocks (counts [B,H,nq])."""
    q = B * H * Sq * hd
    kept = int(np.asarray(counts).sum())
    kv = 2 * kept * bk * hd
    o = B * H * Sq * hd
    return (q + kv + o) * itemsize


def run() -> List[Dict]:
    rows = []
    for (B, H, S, hd) in SHAPES:
        rng = jax.random.PRNGKey(42)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (B, H, S, hd), jnp.float32) * 1.3
        k = jax.random.normal(kk, (B, H, S, hd), jnp.float32) * 1.3
        v = jax.random.normal(kv, (B, H, S, hd), jnp.float32)

        # dense flash kernel vs oracle
        bq = bk = min(128, S)
        out_f = ops.flash(q, k, v, causal=True, block_q=bq, block_k=bk)
        ref_f = kref.flash_attention_ref(q, k, v, causal=True)
        err_f = float(jnp.abs(out_f - ref_f).max())

        # HDP pipeline kernel vs batched-core reference
        hdp = HDPConfig(rho_b=0.5, block_q=bq, block_k=bk, causal=True,
                        head_pruning=False)
        out_h, st = ops.hdp_attention_tpu(q, k, v, hdp, return_stats=True)
        ref_h, _ = hdp_attention(q, k, v, hdp)
        err_h = float(jnp.abs(out_h - ref_h).max())

        nq = S // bq
        dense_b = flash_bytes(B, H, S, S, hd, bq, bk)
        kept_per_row = float(st["kept_blocks_per_row"])
        counts = np.full((B, H, nq), kept_per_row)
        fum_b = fum_bytes(B, H, S, S, hd, bq, bk, counts)
        rows.append({
            "shape": f"{B}x{H}x{S}x{hd}",
            "flash_max_err": f"{err_f:.2e}",
            "hdp_max_err": f"{err_h:.2e}",
            "block_sparsity": round(float(st["block_sparsity"]), 3),
            "dense_hbm_mb": round(dense_b / 1e6, 2),
            "fum_hbm_mb": round(fum_b / 1e6, 2),
            "hbm_saving": round(1 - fum_b / dense_b, 3),
        })
    return rows


def main(quick: bool = False) -> List[Dict]:
    rows = run()
    print("# kernels (interpret-mode correctness + FUM traffic)")
    hdr = list(rows[0].keys())
    print(",".join(hdr))
    for r in rows:
        print(",".join(str(r[h]) for h in hdr))
    return rows


if __name__ == "__main__":
    main()
