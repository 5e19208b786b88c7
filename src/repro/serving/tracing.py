"""Host spans of the serving engine, on the profiler's clock.

``SpanRecorder.span(name)`` times a block of host code two ways at once:
it enters ``jax.profiler.TraceAnnotation(name)``, so a running profiler
records the span in its host plane on the same clock as the device's
ops, and it adds the block's ``time.perf_counter`` duration and one count
to the recorder's per-name table. With no profiler running the annotation
costs one check, so the recorder is always on: "tracing off" means only
that no profiler is running.

Span names carry no attributes: ``TraceAnnotation(name, **kw)`` encodes
keyword arguments into the event's name, which would stop matching.
"""
from __future__ import annotations

import time
from typing import Dict

import jax

_now = time.perf_counter


class Span:
    """One timed block; ``s`` holds its duration once the block exits."""

    __slots__ = ("_rec", "_name", "_ann", "_t0", "s")

    def __init__(self, rec: "SpanRecorder", name: str):
        self._rec, self._name = rec, name
        self._ann = jax.profiler.TraceAnnotation(name)
        self.s = 0.0

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self._t0 = _now()
        return self

    def __exit__(self, *exc) -> None:
        self.s = _now() - self._t0
        self._ann.__exit__(*exc)
        tot = self._rec._tot
        row = tot.get(self._name)
        if row is None:
            tot[self._name] = [self.s, 1]
        else:
            row[0] += self.s
            row[1] += 1


class SpanRecorder:
    """Per-name totals of the spans entered through ``span``."""

    def __init__(self):
        self._tot: Dict[str, list] = {}

    def span(self, name: str) -> Span:
        return Span(self, name)

    def reset(self) -> None:
        self._tot = {}

    def total(self, name: str) -> float:
        """Seconds spent in ``name`` since the last reset (0 if none)."""
        row = self._tot.get(name)
        return row[0] if row else 0.0

    def totals(self) -> Dict[str, Dict[str, float]]:
        return {k: {"s": s, "n": n} for k, (s, n) in self._tot.items()}
