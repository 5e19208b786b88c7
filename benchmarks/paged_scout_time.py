"""Device time of the decode scout alone, on a TPU: the paged scout
kernel against the XLA scout it replaces.

    python3 benchmarks/paged_scout_time.py [--reps N] [--out FILE]

At the serving cells' shapes — qwen2-1.5b (2 kv heads x 6 query heads,
head_dim 128, 128-token pages) over the layer-stacked int8 pool of 961
pages and 28 layers — each timing is one program that scouts all 28
layers (a ``fori_loop`` over the layer index, as the decode program's
layer scan does), ``block_until_ready`` inside the timed region, the
median of ``--reps`` after a warm-up, reported in microseconds per
28-layer program. Both scouts run through the shared decision
(``core.hdp.scout_decision``), so each timing is stage 1 whole:

* ``xla``: gather every table column out of the pool, widen and truncate
  the codes, one float32 einsum over the whole table, pool and decide
  (the route the model takes for verify rows, drafts and windows);
* ``ppb<n>``: the paged scout kernel with ``n`` columns a block, swept
  around the block size it derives from the shapes.

Each cell is timed at a sweep of allocated columns (every slot alike),
which splits the kernel's time into a fixed part and a part per column,
and at the cell's spread of cached tokens per slot (``spread``). At the
spread, layer 0's theta, keep mask and head gate of the kernel are
compared with the XLA scout's (``bitwise_equal``).

The last line of standard output is one JSON object with every timing
and the device; off a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.config import HDPConfig  # noqa: E402
from repro.core.hdp import decode_scout, scout_decision  # noqa: E402
from repro.core.quant import pool_view_finite  # noqa: E402
from repro.kernels import hdp_paged_scout as kern  # noqa: E402
from repro.models.attention import (_fixed_split, _mask_bias,  # noqa: E402
                                    gather_pages)

L, P, N, G, HD, PS = 28, 961, 2, 6, 128, 128
#: (name, slots, table columns, allocated columns a slot: the sweep, and
#: the cell's spread of cached tokens a slot as (low, high))
CELLS = (("decode-long", 12, 80, (0, 16, 32, 48, 64, 80), (2048, 10240)),
         ("chat", 26, 36, (0, 2, 4, 8, 16, 36), (64, 1280)))
HDP = HDPConfig(block_q=1, block_k=PS, rho_b=0.5, tau_h=0.0, causal=True,
                head_pruning=True, normalize_head_score=True, calib="none")


def _inputs(rng, B, mk, kv_len):
    """A page table whose first ceil(kv_len / PS) columns name distinct
    pool pages (the rest scratch page 0), and the extents."""
    kv_len = np.asarray(kv_len, np.int32)
    counts = -(-kv_len // PS)
    pages = rng.permutation(np.arange(1, P, dtype=np.int32))[:B * mk]
    table = pages.reshape(B, mk)
    table[np.arange(mk)[None] >= counts[:, None]] = 0
    return jnp.asarray(table), jnp.asarray(kv_len)


def _xla_layer(iq, kp, table, kv_len, layer):
    B, nP = table.shape
    k = pool_view_finite(gather_pages(kp, table, layer), HDP.int_bits)
    ik = jnp.trunc(k.reshape(B, nP * PS, N, HD))
    s = jnp.einsum("bngqh,bsnh->bngqs", iq, ik,
                   preferred_element_type=jnp.float32)
    q_pos = (kv_len - 1)[:, None, None, None]
    ar = jnp.arange(nP * PS)
    k_pos = jnp.where(ar[None] < kv_len[:, None], ar, -1)[:, None, None]
    return decode_scout(s, _mask_bias(q_pos, k_pos, True, 0), HDP)


def _kernel_layer(ppb):
    def run(iq, kp, table, kv_len, layer):
        counts = (kv_len + PS - 1) // PS
        theta = kern._paged_scout(iq[..., 0, :], kp, table, counts, kv_len,
                                  layer, ppb=ppb, int_bits=HDP.int_bits,
                                  interpret=False)
        bvalid = (jnp.arange(table.shape[1]) * PS
                  < kv_len[:, None])[:, None, None]
        return scout_decision(theta, bvalid, kv_len[:, None, None], HDP)
    return run


def _program(layer_fn):
    """One jitted program that scouts all L layers."""
    def prog(iq, kp, table, kv_len):
        def body(layer, acc):
            keep, _, theta, _, head_kept = layer_fn(iq, kp, table, kv_len,
                                                    layer)
            return (acc + theta.sum() + keep.sum()
                    + head_kept.sum().astype(jnp.float32))
        return jax.lax.fori_loop(0, L, body, jnp.float32(0))
    return jax.jit(prog)


def _timer(fn, args, reps):
    jax.block_until_ready(fn(*args))                  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e6                # us per program


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    kp = jax.random.randint(jax.random.fold_in(key, 0), (L, P, N, PS, HD),
                            -128, 128, jnp.int8)
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "unit": "us per 28-layer program", "cells": {}}
    for name, B, mk, sweep, (lo, hi) in CELLS:
        q = 6.0 * jax.random.normal(jax.random.fold_in(key, 1),
                                    (B, N, G, 1, HD))
        _, iq, _ = _fixed_split(q, HDP)
        ppb0 = kern.scout_pages_per_block(mk, kp)
        ppbs = sorted({2, 4, ppb0, 2 * ppb0, 16} & set(range(1, mk + 1)))
        progs = {"xla": _program(_xla_layer)}
        progs.update({f"ppb{p}": _program(_kernel_layer(p)) for p in ppbs})
        cell = {"B": B, "mk": mk, "ppb": ppb0, "sweep": {}, "spread": {}}
        points = [("sweep", c, [c * PS] * B) for c in sweep]
        points.append(("spread", f"{lo}-{hi}",
                       rng.integers(lo, hi + 1, size=B).tolist()))
        for kind, label, kv_len in points:
            table, lens = _inputs(rng, B, mk, kv_len)
            a = (iq, kp, table, lens)
            row = {k: _timer(f, a, args.reps) for k, f in progs.items()}
            row["columns_walked"] = int(np.sum(-(-np.asarray(kv_len) // PS)))
            if kind == "spread":
                got = jax.jit(_kernel_layer(ppb0))(iq, kp, table, lens, 0)
                want = jax.jit(_xla_layer)(iq, kp, table, lens, 0)
                row["bitwise_equal"] = all(
                    np.array_equal(np.broadcast_to(np.asarray(g), w.shape),
                                   np.asarray(w))
                    for g, w in zip(got, want))
            cell[kind][str(label)] = row
            print(name, kind, label, json.dumps(row), flush=True)
        result["cells"][name] = cell
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
