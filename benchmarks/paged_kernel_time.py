"""Device time of the paged decode kernel alone, on a TPU.

    python3 benchmarks/paged_kernel_time.py [--baseline PATH] [--out FILE]

Times ``hdp_paged_fum_decode`` at the serving cells' shapes — qwen2-1.5b
(2 kv heads x 6 query heads, head_dim 128, 128-token pages) over the
layer-stacked int8 pool of 961 pages and 28 layers — one program of 28
calls (one per layer) per timing, ``block_until_ready`` inside the timed
region, the median of ``--reps`` after a warm-up. Kept pages per row are
swept so that the time splits into a fixed part and a part per kept
page; the compute block size (``ppb``) is swept around the one the
kernel derives from the shapes.

``--baseline`` names another version of ``kernels/hdp_paged_decode.py``
whose kernel takes the per-row keep laid out [B, mk, N, G, Sq] (a grid
over slots x kv heads x table columns); it is timed on the same inputs,
and its outputs are compared with this kernel's. At each cell's spread of
kept pages, layer 0's output of each kernel is compared with a float64
reference computed on the host (relative L2 error).

The last line of standard output is one JSON object with every timing
(microseconds per call) and the device; off a TPU the script exits
non-zero.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.quant import pool_scale  # noqa: E402
from repro.kernels import hdp_paged_decode as kern  # noqa: E402

L, P, N, G, HD, PS = 28, 961, 2, 6, 128, 128
#: (name, slots, table columns, kept pages per row: the sweep, and the
#: cell's spread of kept pages as (low, high))
CELLS = (("decode-long", 12, 80, (0, 8, 16, 32, 48, 80), (16, 48)),
         ("chat", 26, 36, (0, 2, 4, 8, 16, 36), (2, 6)))
KEEP_SHARE = 0.7     # share of a fetched page's (head, row) pairs kept


def _inputs(rng, B, mk, counts):
    """Page lists, per-head keep and extents for kept-page ``counts``."""
    page_ids = np.zeros((B, mk), np.int32)
    logical = np.zeros((B, mk), np.int32)
    for b, c in enumerate(counts):
        logical[b, :c] = np.sort(rng.choice(mk, size=c, replace=False))
        page_ids[b, :c] = rng.choice(np.arange(1, P), size=c, replace=False)
    keep = rng.random((B, N, G, 1, mk)) < KEEP_SHARE
    keep[:, 0, 0, 0, :] = True            # every listed page is fetched
    return (jnp.asarray(page_ids), jnp.asarray(logical),
            jnp.asarray(np.asarray(counts, np.int32)),
            jnp.asarray(keep.astype(np.int32)),
            jnp.full((B,), mk * PS, jnp.int32))


def _reference(qq, kp, vp, scale, pid, lg, cnt, keep, kv_len):
    """Layer 0 of the approximate attention in float64 on the host."""
    qq, kp, vp = (np.asarray(x, np.float64) for x in (qq, kp[0], vp[0]))
    pid, lg, cnt, keep, kv_len = (np.asarray(x) for x in
                                  (pid, lg, cnt, keep, kv_len))
    out = np.zeros(qq.shape)
    for b in range(qq.shape[0]):
        c = int(cnt[b])
        if c == 0:
            continue
        pos = (lg[b, :c, None] * PS + np.arange(PS)).reshape(-1)
        for n in range(N):
            k = kp[pid[b, :c], n].reshape(-1, HD) * scale
            v = vp[pid[b, :c], n].reshape(-1, HD) * scale
            q = qq[b, n, :, 0]                              # [G, hd]
            fq, fk = q - np.trunc(q), k - np.trunc(k)
            s = (q @ k.T - fq @ fk.T) / np.sqrt(HD)
            ok = np.repeat(keep[b, n, :, 0, :c], PS, axis=1) > 0
            ok &= pos[None] < kv_len[b]
            s = np.where(ok, s, -np.inf)
            m = s.max(-1, keepdims=True)
            p = np.where(ok, np.exp(s - np.where(np.isfinite(m), m, 0)), 0)
            out[b, n, :, 0] = p @ v / np.maximum(p.sum(-1, keepdims=True),
                                                 1e-30)
    return out


def _rel_err(x, ref):
    return float(np.linalg.norm(np.asarray(x, np.float64) - ref)
                 / np.linalg.norm(ref))


def _timer(fn, args, reps):
    jax.block_until_ready(fn(*args))                  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) / L * 1e6            # us per call


def _program(call, layers=L):
    """One jitted program of ``layers`` kernel calls, one per layer."""
    def prog(qq, kp, vp, ks, vs, pid, lg, cnt, keep, kv_len):
        out = jnp.zeros(qq.shape, jnp.float32)
        for layer in range(layers):
            out = out + call(qq, kp, vp, ks, vs, pid, lg, cnt, keep, kv_len,
                             jnp.int32(layer))
        return out
    return jax.jit(prog)


def _new(ppb, layers=L):
    def call(qq, kp, vp, ks, vs, pid, lg, cnt, keep, kv_len, layer):
        return kern._paged_fum(qq, kp, vp, pid, lg, cnt, keep, kv_len, layer,
                               ks, vs, ppb=ppb, approx=True, int_bits=4,
                               frac_bits=12, interpret=False)
    return _program(call, layers)


def _baseline(path, layers=L):
    spec = importlib.util.spec_from_file_location("baseline_kernel", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def call(qq, kp, vp, ks, vs, pid, lg, cnt, keep, kv_len, layer):
        return mod.hdp_paged_fum_decode(
            qq, kp, vp, pid, lg, cnt, jnp.moveaxis(keep, -1, 1), kv_len,
            k_scale=ks, v_scale=vs, layer=layer)
    return _program(call, layers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--baseline", default=None)
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
    shape = (L, P, N, PS, HD)
    kp = jax.random.randint(jax.random.fold_in(key, 0), shape, -127, 128,
                            jnp.int8)
    vp = jax.random.randint(jax.random.fold_in(key, 1), shape, -127, 128,
                            jnp.int8)
    ks = jnp.full((P, N), pool_scale(4), jnp.float32)
    base = _baseline(args.baseline) if args.baseline else None
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "cells": {}}
    for name, B, mk, sweep, (lo, hi) in CELLS:
        q = jax.random.normal(jax.random.fold_in(key, 2), (B, N, G, 1, HD))
        qq = jnp.round(q * 4096) / 4096
        ppb0 = kern.pages_per_block(mk, kp, vp)
        ppbs = sorted({1, 2, 4, ppb0, 2 * ppb0, 16} & set(range(1, mk + 1)))
        progs = {f"ppb{p}": _new(p) for p in ppbs}
        if base is not None:
            progs["baseline"] = base
        one = {"new": _new(ppb0, 1)}
        if args.baseline:
            one["baseline"] = _baseline(args.baseline, 1)
        cell = {"B": B, "mk": mk, "ppb": ppb0, "sweep": {}, "cell": {}}
        points = [("sweep", c, [c] * B) for c in sweep]
        points.append(("cell", f"{lo}-{hi}",
                       rng.integers(lo, hi + 1, size=B).tolist()))
        for kind, label, counts in points:
            inp = _inputs(rng, B, mk, counts)
            a = (qq, kp, vp, ks, ks) + inp
            row = {k: _timer(f, a, args.reps) for k, f in progs.items()}
            if base is not None:
                ref = np.asarray(progs["baseline"](*a))
                new = np.asarray(progs[f"ppb{ppb0}"](*a))
                row["max_abs_diff"] = float(np.max(np.abs(new - ref)))
            if kind == "cell":
                ref = _reference(qq, kp, vp, pool_scale(4), *inp)
                for k, f in one.items():
                    row[f"rel_err_{k}"] = _rel_err(f(*a), ref)
            row["kept_pages"] = int(sum(counts))
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
