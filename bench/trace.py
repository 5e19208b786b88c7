"""Reduction of a profiler trace to the benchmark's device numbers.

``load_xplane`` turns the JAX profiler's ``.xplane.pb`` into a small
normalized record — per chip the device's op events and program (module)
events, plus the harness's own host spans (``bench.*``), as
``[name, start_ns, duration_ns]``, an op with its HLO opcode as a fourth
element — and ``reduce`` computes everything from that record alone, so a
recorded trace checks the reduction without a chip.

Collective time is the device time of the ops that move data between
chips: those whose HLO opcode is one of ``COLLECTIVES``, or the
``-start`` / ``-done`` half of an asynchronous one, whatever the op is
named (``psum`` lowers to ``%psum.N = ... all-reduce(...)``). The TPU
compiler also wraps each half of an asynchronous collective in a fusion
of its own (``%async-collective-start = ... fusion(...), kind=kCustom``);
those count too. An asynchronous transfer's overlap with compute counts
only through its start and done ops: the time between them, in which
other ops run, is not collective time.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

#: ops that hold other ops (a scanned layer loop, a call): their time is
#: their body's, so the breakdown lists the body's ops instead
CONTAINERS = ("%while", "%conditional", "%call")
#: HLO opcodes of the collectives
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_OPCODE = re.compile(r"\s*([a-z][a-z0-9-]*)\(")
_FUSED_ASYNC = re.compile(r"%?async-collective-(start|done)(\.\d+)?$")


def hlo_opcode(text: str) -> str:
    """The opcode of one HLO instruction's text, ``%name = shape
    opcode(operands), attributes`` (a tuple shape in parentheses); ""
    where the text has none."""
    _, eq, rest = text.partition(" = ")
    if not eq:
        return ""
    if rest.startswith("("):                  # tuple shape: to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
        if i < 0:
            return ""
    m = _OPCODE.match(rest, i)
    return m.group(1) if m else ""


def is_collective(name: str, opcode: str) -> bool:
    """Whether the op ``name`` of HLO ``opcode`` moves data between chips:
    a collective, either half of an asynchronous one, or the fusion the
    TPU compiler wraps such a half in."""
    if re.sub(r"-(start|done)$", "", opcode) in COLLECTIVES:
        return True
    return opcode == "fusion" and bool(_FUSED_ASYNC.match(name))


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
                    ev = [e.name, float(e.start_ns), float(e.duration_ns)]
                    if dst is ops:
                        # an op's event name is its HLO text: keep
                        # "%name.N" and the opcode
                        ev[0] = e.name.split(" = ")[0]
                        ev.append(hlo_opcode(e.name))
                    dst.append(ev)
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
    kernel_n: Dict[str, int] = field(default_factory=dict)     # chip 0
    collective_s: float = 0.0              # mean over chips
    collective_n: int = 0                  # chip 0
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
    Times are means over the chips and counts those of the first chip
    (each chip runs every program, on its share of the work);
    ``collective_s`` / ``collective_n`` cover the ops ``is_collective``
    names, from the opcode the record keeps beside each op's name.
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
                    if ci == 0:
                        red.kernel_n[k] = red.kernel_n.get(k, 0) + 1
            if is_collective(e[0], e[3] if len(e) > 3 else ""):
                red.collective_s += d / len(chips)
                if ci == 0:
                    red.collective_n += 1
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
