"""Trace reduction: idle share, per-program and kernel time, breakdown."""
import json
from pathlib import Path

import pytest

from bench.run import KERNELS, PROGRAMS
from bench.trace import hlo_opcode, is_collective, reduce

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


def test_four_chip_reduction_counts_calls_once_and_collective_time():
    """Four chips each run the same program on their share: a kernel call
    is counted once (on the first chip), times are means over the chips,
    and collective time covers a synchronous all-reduce renamed ``psum``,
    an async all-gather's start and done (not the compute between them)
    and the fusions the TPU compiler wraps an async collective's halves in;
    an op named like a collective but of another opcode does not count."""
    def chip(i, k):
        return {"name": f"/device:TPU:{i}",
                "ops": [["%hdp_paged_fum_decode.3", 0, 4 + k, "custom-call"],
                        ["%hdp_paged_fum_decode.3", 10, 4 + k, "custom-call"],
                        ["%psum.7", 20, 2 + k, "all-reduce"],
                        ["%all-gather-start.1", 30, 1, "all-gather-start"],
                        ["%fusion.9", 31, 8, "fusion"],
                        ["%all-gather-done.1", 39, 2, "all-gather-done"],
                        ["%async-collective-start", 45, 1, "fusion"],
                        ["%async-collective-done.2", 48, 3, "fusion"],
                        ["%all-reduce-ish.4", 55, 5, "fusion"],
                        ["%old_recording", 62, 1]],
                "modules": [["jit__decode_loop_paged_fn(7)", 0, 70]]}

    rec = {"chips": [chip(i, i) for i in range(4)],
           "host": [["bench.window", 0, 100], ["bench.step", 0, 70]]}
    red = reduce(rec, (0, 100), programs=PROGRAMS, kernels=KERNELS)
    assert red.kernel_n == {"hdp_paged_fum_decode": 2}
    assert red.kernel_s["hdp_paged_fum_decode"] == pytest.approx(
        2 * (4 + 1.5) * 1e-9)                      # k averages 1.5
    assert red.program_n == {"decode": 1}
    assert red.program_s["decode"] == pytest.approx(70e-9)
    assert red.collective_n == 5
    assert red.collective_s == pytest.approx((2 + 1.5 + 1 + 2 + 1 + 3) * 1e-9)


def test_one_chip_reduction_has_no_collective_time():
    rec = {"chips": [{"name": "/device:TPU:0",
                      "ops": [["%fusion.1", 0, 10, "fusion"],
                              ["%copy.2", 10, 5, "copy"]],
                      "modules": []}], "host": []}
    red = reduce(rec, (0, 20), programs=PROGRAMS, kernels=KERNELS)
    assert (red.collective_s, red.collective_n) == (0.0, 0)


#: HLO text of ops as the TPU compiler emits them (a described v5e:2x2
#: compile of a tensor-parallel layer loop), trimmed after the opcode's
#: operands
TPU_HLO = {
    "%psum.9 = bf16[16,4096]{1,0:T(8,128)(2,1)S(1)} all-reduce("
    "%get-tuple-element.72), channel_id=1, to_apply=%region_1.0": "all-reduce",
    "%reduce_scatter.7 = bf16[64,512]{1,0:T(8,128)(2,1)} reduce-scatter("
    "%fusion), channel_id=1, dimensions={0}": "reduce-scatter",
    "%all-gather-start = (bf16[16,1024]{1,0:T(8,128)(2,1)}, bf16[16,4096]"
    "{1,0:T(8,128)(2,1)}) all-gather-start(%x), dimensions={1}": "all-gather-start",
    "%async-collective-start = (bf16[16,1024]{1,0:T(8,128)(2,1)S(1)}, "
    "s32[2]{0:S(4)}, /*index=5*/u32[]{:S(2)}) fusion(%a, %b), kind=kCustom, "
    "calls=%fused_computation.8": "fusion",
    "%hdp_paged_fum_decode.15 = f32[12,2,8,128]{3,2,1,0} custom-call(%p), "
    "custom_call_target=\"tpu_custom_call\"": "custom-call",
    "%fusion.199": "",
}


@pytest.mark.parametrize("text", list(TPU_HLO))
def test_opcode_is_read_from_the_hlo_text(text):
    assert hlo_opcode(text) == TPU_HLO[text]


@pytest.mark.parametrize("name, opcode, want", [
    ("%psum.9", "all-reduce", True),
    ("%cp.1", "collective-permute-done", True),
    ("%a2a", "all-to-all", True),
    ("%async-collective-done", "fusion", True),
    ("%all-gather.3", "fusion", False),        # named so, but compute
    ("%all-gather.3", "", False),               # a recording without opcodes
])
def test_a_collective_is_known_by_its_opcode(name, opcode, want):
    assert is_collective(name, opcode) is want


#: every field of the recorded trace's reduction before collectives were
#: read (kernel calls then counted on every chip: one chip here)
RECORDED = {
    "window_s": 0.12, "busy_s": 0.110422506,
    "program_s": {"decode": 0.083789898}, "program_n": {"decode": 3},
    "kernel_s": {"hdp_paged_fum_decode": 0.05493838},
    "kernel_n": {"hdp_paged_fum_decode": 111},
    "device_ops": [
        ("%hdp_paged_fum_decode.15", 0.05493838),
        ("%floor_select_fusion.2", 0.010873315000000005),
        ("%copy.1244", 0.009351657000000001),
        ("%bitcast_add_fusion.6", 0.008261288000000002),
        ("%fusion.199", 0.007082377000000002), ("%fusion.212", 0.004232178),
        ("%fusion.201", 0.0024084840000000002), ("%fusion.124", 0.001856731),
        ("%copy.1254", 0.0009492759999999997),
        ("%fusion.211", 0.0008683250000000003)],
    "idle_gaps": [
        ("bench.step", 0.003256518), ("bench.step", 0.002704802),
        ("bench.step", 0.002527496), ("bench.step", 0.000396735),
        ("bench.step", 2.341e-06), ("bench.step", 2.337e-06),
        ("bench.step", 2.334e-06), ("bench.step", 2.32e-06),
        ("bench.step", 2.312e-06), ("bench.step", 2.307e-06)],
}


def test_recorded_one_chip_trace_reduces_as_before():
    rec = json.loads((DATA / "trace_qwen2_decode_long.json").read_text())
    red = reduce(rec, tuple(rec["window"]), programs=PROGRAMS, kernels=KERNELS)
    assert {k: getattr(red, k) for k in RECORDED} == RECORDED
    assert (red.collective_s, red.collective_n) == (0.0, 0)
