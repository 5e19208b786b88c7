"""Reduction of a profiler trace to the benchmark's device numbers.

``load_xplane`` turns the JAX profiler's ``.xplane.pb`` into a small
normalized record — per chip the device's op events and program (module)
events, plus the harness's own host spans (``bench.*``), as
``[name, start_ns, duration_ns]`` — and ``reduce`` computes everything
from that record alone, so a recorded trace checks the reduction without
a chip.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

#: ops that hold other ops (a scanned layer loop, a call): their time is
#: their body's, so the breakdown lists the body's ops instead
CONTAINERS = ("%while", "%conditional", "%call")


def load_xplane(trace_dir: str) -> dict:
    """Normalized record of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    chips, host, lines = [], [], {}
    for plane in pd.planes:
        lines[plane.name] = [line.name for line in plane.lines]
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dst is None:
                    continue
                for e in line.events:
                    # an op's event name is its HLO text: keep "%name.N"
                    name = e.name.split(" = ")[0] if dst is ops else e.name
                    dst.append([name, float(e.start_ns), float(e.duration_ns)])
            chips.append({"name": plane.name, "ops": ops, "modules": modules})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    chips.sort(key=lambda c: c["name"])
    return {"chips": chips, "host": host, "lines": lines}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(ev: Sequence, t0: float, t1: float) -> Optional[Tuple[float, float]]:
    s, e = max(ev[1], t0), min(ev[1] + ev[2], t1)
    return (s, e) if e > s else None


@dataclass
class Reduction:
    """Device numbers of one traced window (seconds)."""

    window_s: float
    busy_s: float                          # mean over chips
    program_s: Dict[str, float] = field(default_factory=dict)
    program_n: Dict[str, int] = field(default_factory=dict)
    kernel_s: Dict[str, float] = field(default_factory=dict)
    kernel_n: Dict[str, int] = field(default_factory=dict)
    device_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(record: dict, window: Tuple[float, float], *,
           programs: Dict[str, Sequence[str]], kernels: Sequence[str],
           top: int = 10) -> Reduction:
    """Reduce ``record`` over ``window`` = (t0_ns, t1_ns).

    ``programs`` maps a label to the substrings of the compiled-program
    names it covers (e.g. ``{"decode": ["_decode_loop_paged_fn"]}``);
    ``kernels`` lists kernel names, matched as substrings of op names.
    ``device_ops`` lists the ops that took most device time, containers
    (a scanned loop, a call) left out.
    Busy time is the union of the device's op intervals; idle gaps are
    the holes in it, each labelled with the harness span the host was in
    at the gap's middle (``host.none`` outside every span)."""
    t0, t1 = window
    chips = record["chips"]
    if not chips:
        raise ValueError("trace has no TPU device plane")
    red = Reduction(window_s=(t1 - t0) / 1e9, busy_s=0.0)
    op_tot: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for ci, chip in enumerate(chips):
        iv = [c for c in (_clip(e, t0, t1) for e in chip["ops"]) if c]
        busy = _union(iv)
        red.busy_s += sum(e - s for s, e in busy) / 1e9 / len(chips)
        if ci == 0:
            edges = [t0] + [x for se in busy for x in se] + [t1]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
        for e in chip["ops"]:
            c = _clip(e, t0, t1)
            if not c:
                continue
            d = (c[1] - c[0]) / 1e9
            op_tot[e[0]] = op_tot.get(e[0], 0.0) + d / len(chips)
            for k in kernels:
                if k in e[0]:
                    red.kernel_s[k] = red.kernel_s.get(k, 0.0) + d / len(chips)
                    red.kernel_n[k] = red.kernel_n.get(k, 0) + 1
        for e in chip["modules"]:
            c = _clip(e, t0, t1)
            if not c:
                continue
            for label, keys in programs.items():
                if any(k in e[0] for k in keys):
                    red.program_s[label] = (red.program_s.get(label, 0.0)
                                            + (c[1] - c[0]) / 1e9 / len(chips))
                    if ci == 0:
                        red.program_n[label] = red.program_n.get(label, 0) + 1
    ops = {k: v for k, v in op_tot.items()
           if not k.startswith(CONTAINERS)}
    red.device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    host = sorted(record["host"], key=lambda e: e[2])   # innermost first

    def label(mid: float) -> str:
        for name, s, d in host:
            if s <= mid <= s + d and name != "bench.window":
                return name
        return "host.none"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    red.idle_gaps = [(label((s + e) / 2), (e - s) / 1e9) for s, e in longest]
    return red
