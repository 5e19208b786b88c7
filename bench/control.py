"""Readings that set a cell's correctness limit, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <s>

For each seed it runs the cell as ``bench/run.py`` does (the engine at the
cell's own size, a window of ``--seconds``, then the float32 reference
over the sampled requests) and prints one JSON line with the numbers
compared. On a control seed the control takes the program's place in that
comparison: the reference one precision step below the configuration's
dtype, read (in the float32 reference) at the token it puts first at each
served position; its line has ``correct`` as the harness decides it for
the control, and the program's numbers of the same run beside them. The
limit in ``bench/limits/<cell>.json`` is set between the largest program
reading and the smallest control reading. The benchmark's own runs never
run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import NoChip, load_cell, log, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            out = run(cell, seed, args.seconds, False, control=seed in ctrl)
        except NoChip as e:
            log(f"control: {e}")
            return 3
        row = {"seed": seed, "judged": "control" if seed in ctrl else "program",
               "correct": out["correct"],
               "check": {k: v["value"] for k, v in out["check"].items()},
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        if seed in ctrl:
            row["program"] = out["program"]
            row["requests"] = out["check_requests"]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
