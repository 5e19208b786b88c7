"""Bench regression gate: fail CI on a serving decode-throughput cliff.

    PYTHONPATH=src python -m benchmarks.check_regression \
        --baseline bench_baseline_committed.json \
        --fresh BENCH_serving.json [--max-regress 0.20]

Compares the ``current`` row block of a freshly produced
BENCH_serving.json against the ``current`` block of the *committed* copy
(saved aside before the bench run overwrites the file), row-matched by
(bench, arch, hdp, backend, decode_horizon, attn_policy, kv_dtype,
tp, dp) — the policy component is "static" on every row (older rows
carry no attn_policy and normalize to it), the
kv_dtype component keeps serving_kvquant's int8-vs-fp32 legs apart
(rows from before the quantized pool normalize to "fp32"), and the
tp/dp components keep serving_tp's mesh legs apart (pre-mesh rows
normalize to tp=1, dp=1). The gate trips when the
MEAN decode_tok_s ratio across comparable rows drops below
``1 - max_regress`` — per-row wall-clock on shared CI runners is too
noisy to gate on individually, but a >20% mean collapse across every
serving bench is a real perf cliff, not scheduler jitter.

Exit codes: 0 = pass (or nothing comparable — a loud note is printed so
a silently-empty comparison cannot masquerade as a green gate), 1 =
regression, 2 = usage/IO error. Stdlib only: the gate must run before
any dependency install step can break.
"""
from __future__ import annotations

import argparse
import json
import sys


def _load_rows(path: str):
    """(quick flag, rows) of the file's ``current`` block."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        print(f"!! check_regression: cannot read {path}: {e}")
        return None, []
    cur = data.get("current") or {}
    return cur.get("quick"), cur.get("rows") or []


def _key(row: dict):
    # rows without an attn_policy ran static selection, as every row
    # does now, rows recorded before the
    # quantized KV pool carry no kv_dtype (they all served the fp32
    # pool), rows recorded before mesh-sharded serving carry no tp/dp
    # (they all served one unsharded engine), and rows recorded before
    # the fault-injection harness carry no faults field (they all ran
    # clean); normalizing all of these keeps old baselines comparable
    # — and keeps a chaos leg from ever being compared against a clean
    # one, since the fault plan is part of the cell identity
    return (row.get("bench"), row.get("arch"), row.get("hdp"),
            row.get("backend"), row.get("decode_horizon"),
            row.get("attn_policy") or "static",
            row.get("kv_dtype") or "fp32",
            row.get("tp") or 1, row.get("dp") or 1,
            row.get("faults") or "none")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", required=True,
                    help="committed BENCH_serving.json (copied aside "
                         "before the bench run)")
    ap.add_argument("--fresh", required=True,
                    help="BENCH_serving.json produced by this run")
    ap.add_argument("--max-regress", type=float, default=0.20,
                    help="maximum tolerated mean decode tok/s drop "
                         "(fraction; default 0.20)")
    args = ap.parse_args(argv)

    base_quick, base_rows = _load_rows(args.baseline)
    fresh_quick, fresh_rows = _load_rows(args.fresh)
    if not base_rows or not fresh_rows:
        print("## check_regression: NOTHING COMPARABLE (missing or empty "
              "row blocks) — gate passes vacuously; fix the bench artifacts "
              "so it bites again")
        return 0
    if base_quick != fresh_quick:
        print(f"## check_regression: NOTHING COMPARABLE — baseline rows "
              f"were recorded with quick={base_quick}, fresh rows with "
              f"quick={fresh_quick}; refresh the committed "
              f"BENCH_serving.json at this run's settings so the gate "
              f"bites again")
        return 0

    base_by_key = {}
    for r in base_rows:
        if r.get("decode_tok_s"):
            base_by_key.setdefault(_key(r), r)
    ratios = []
    for r in fresh_rows:
        b = base_by_key.get(_key(r))
        if b is None or not r.get("decode_tok_s"):
            continue
        ratio = r["decode_tok_s"] / b["decode_tok_s"]
        ratios.append(ratio)
        flag = "  <-- slow" if ratio < 1.0 - args.max_regress else ""
        print(f"{'/'.join(str(k) for k in _key(r))}: "
              f"{b['decode_tok_s']:.2f} -> {r['decode_tok_s']:.2f} tok/s "
              f"(x{ratio:.2f}){flag}")
    if not ratios:
        print("## check_regression: NOTHING COMPARABLE (no matching rows "
              "with decode_tok_s) — gate passes vacuously; check the row "
              "keys if benches were renamed")
        return 0

    mean = sum(ratios) / len(ratios)
    floor = 1.0 - args.max_regress
    print(f"## mean decode tok/s ratio over {len(ratios)} comparable rows: "
          f"x{mean:.3f} (floor x{floor:.2f})")
    if mean < floor:
        print(f"!! REGRESSION: mean decode throughput fell "
              f"{1 - mean:.0%} vs the committed baseline "
              f"(> {args.max_regress:.0%} tolerated)")
        return 1
    print("## check_regression: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
