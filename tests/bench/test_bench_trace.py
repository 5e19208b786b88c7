"""Trace reduction: idle share, per-program and kernel time, breakdown."""
import json
from pathlib import Path

import pytest

from bench.run import KERNELS, PROGRAMS
from bench.trace import reduce

DATA = Path(__file__).parent / "data"


def test_reduction_of_a_hand_made_trace():
    rec = {"chips": [{"name": "/device:TPU:0",
                      "ops": [["%fusion.1", 0, 10], ["%fusion.2", 5, 10],
                              ["%hdp_paged_fum_decode.3", 30, 5],
                              ["%while.4", 0, 15], ["%late", 60, 5]],
                      "modules": [["jit__decode_loop_paged_fn(7)", 0, 20],
                                  ["jit__prefill_chunk_fn(2)", 28, 10],
                                  ["jit__other(1)", 40, 5]]}],
           "host": [["bench.window", 0, 50], ["bench.step", 0, 25],
                    ["bench.results", 25, 10], ["bench.idle", 50, 50]]}
    red = reduce(rec, (0, 50), programs=PROGRAMS, kernels=KERNELS)
    assert red.window_s == pytest.approx(50e-9)
    assert red.busy_s == pytest.approx(20e-9)           # [0,15] + [30,35]
    assert red.idle_share == pytest.approx(0.6)
    assert red.program_s == pytest.approx({"decode": 20e-9, "prefill": 10e-9})
    assert red.program_n == {"decode": 1, "prefill": 1}
    assert red.kernel_s == pytest.approx({"hdp_paged_fum_decode": 5e-9})
    assert [n for n, _ in red.device_ops] == [
        "%fusion.1", "%fusion.2", "%hdp_paged_fum_decode.3"]   # no %while
    assert red.idle_gaps == [("bench.step", pytest.approx(15e-9)),
                             ("host.none", pytest.approx(15e-9))]


def busy_by_timeline(ops, t0, t1, step):
    """Busy time by marking every ``step`` ns of the window an op covers."""
    import numpy as np

    mark = np.zeros(int((t1 - t0) // step) + 1, bool)
    for _, s, d in ops:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            mark[int((a - t0) // step):int(-(-(b - t0) // step))] = True
    return mark.sum() * step


def test_reduction_of_a_recorded_chip_trace():
    """A quarter second of a traced qwen2-1.5b.decode-long window on one
    TPU v5e: busy time agrees with a brute-force timeline, the decode
    program and the paged decode kernel are found by name, and every
    share stays within the window."""
    rec = json.loads((DATA / "trace_qwen2_decode_long.json").read_text())
    t0, t1 = rec["window"]
    red = reduce(rec, (t0, t1), programs=PROGRAMS, kernels=KERNELS)
    ops = [e for e in rec["chips"][0]["ops"]]
    brute = busy_by_timeline(ops, t0, t1, 100.0) / 1e9
    assert red.busy_s == pytest.approx(brute, rel=0.02)
    assert 0 < red.busy_s <= red.window_s
    assert red.program_n["decode"] >= 3
    assert 0 < red.kernel_s["hdp_paged_fum_decode"] < red.program_s["decode"]
    assert red.program_s["decode"] <= red.window_s
    assert len(red.device_ops) == 10 and len(red.idle_gaps) == 10
    assert all(name.startswith("bench.") or name == "host.none"
               for name, _ in red.idle_gaps)
