"""Rate sweep of an open-loop cell: find the highest arrival rate the
engine sustains, once, on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --rates 3,4,5 \
        --seconds 20

For each rate it runs the cell as ``bench/run.py`` does, with that rate in
place of the mix's ``rate_per_s``, and prints one JSON line with the
cell's end-to-end metrics and ``ttft_p95_s``. The comparison with the
reference is skipped: a sweep judges the load, not the outputs. The knee
is the highest rate at which ``ttft_p95_s`` stays near its low-load
value; the cell's mix then runs at about four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as R


def _no_check(*_a, **_k) -> dict:
    return {"mean_gap": 0.0, "widest_gap": 0.0, "off_argmax": 0.0,
            "tokens": 0, "requests": []}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    if all(m["name"] != "ttft_p95_s" for m in cell.end_to_end):
        cell.end_to_end.append({"name": "ttft_p95_s"})
    R.reference_check = _no_check
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.mix["rate_per_s"] = rate
        try:
            out = R.run(cell, args.seed, args.seconds, False)
        except R.NoChip as e:
            R.log(f"sweep: {e}")
            return 3
        print(json.dumps({"rate_per_s": rate, "attempted": out["attempted"],
                          "failed": out["failed"],
                          "metrics": {k: v["value"]
                                      for k, v in out["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
