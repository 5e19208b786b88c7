"""The benchmark's tests import ``bench`` from the checkout's root."""
import sys
from pathlib import Path

ROOT = str(Path(__file__).resolve().parents[2])
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import json  # noqa: E402

import pytest  # noqa: E402

#: float32 tiny configuration: the engine and the reference agree to
#: rounding, and its control (bfloat16 matmul inputs) does not
TINY_LIMIT = 1e-3


class StepClock:
    """The harness's clock in a test: it moves ``step_s`` per engine step,
    so a window holds the same work however loaded the host is."""

    def __init__(self, step_s):
        self.now, self.step_s = 0.0, step_s

    def perf_counter(self):
        return self.now

    def sleep(self, s):
        self.now += s


def tiny_runner(monkeypatch, config="tiny.json", chips=1):
    """Runs of a tiny float32 closed-loop cell on the CPU through the
    whole harness (the chip check skipped), on a clock that counts engine
    steps: ``go(seed, seconds, control)`` returns the result line. The
    configuration is ``tests/bench/data/<config>``; ``chips`` of the
    process's devices serve it."""
    import jax

    from bench import run as R

    clock = StepClock(0.01)
    step = R.Driver.step

    def timed_step(self):
        clock.now += clock.step_s
        return step(self)

    monkeypatch.setattr(R, "time", clock)
    monkeypatch.setattr(R.Driver, "step", timed_step)

    root = Path(ROOT)
    cfg = json.loads((root / "tests/bench/data" / config).read_text())
    mix = json.loads((root / "bench/traffic/decode-long.json").read_text())
    mix.update(prompt={"dist": "uniform", "lo": 300, "hi": 700},
               output={"dist": "uniform", "lo": 20, "hi": 60}, requests=64,
               check={"requests": 2, "max_served_tokens": 200})
    spec = json.loads((root / "BENCHMARK.json").read_text())
    name = "qwen2-1.5b.decode-long"
    cell = R.Cell(name, chips, cfg, mix, R._for_cell(spec["end_to_end"], name),
                  R._for_cell(spec["per_layer"], name),
                  {"mean_gap": {"limit": TINY_LIMIT}, "min_tokens": 20})
    peaks = json.loads((root / "bench/peaks.json").read_text())["TPU v5 lite"]

    def go(seed=5, seconds=2.0, control=False):
        return R.run(cell, seed, seconds, False, control=control,
                     devices=jax.devices()[:chips], peaks=peaks)
    return go


@pytest.fixture
def tiny_run(monkeypatch):
    """``tiny_runner`` of the one-chip tiny cell."""
    return tiny_runner(monkeypatch)
