"""Adaptive speculation: :mod:`repro.autotune.speculation` keeps an
acceptance-rate EMA and sets the speculative draft length and the draft
prune aggressiveness per round (``Engine(adaptive_spec=True)``).
"""
from repro.autotune.speculation import SpecConfig, SpecController

__all__ = ["SpecConfig", "SpecController"]
