"""Traffic generation and the harness's clocks, without a chip."""
import json
import math
import time
from pathlib import Path

import numpy as np
import pytest

from bench import run as R
from bench.traffic import Traffic, quantiles

ROOT = Path(__file__).resolve().parents[2]


def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["decode-long", "chat-poisson"])
def test_same_seed_same_traffic(name):
    kw = dict(slots=12, vocab=1000, max_len=10240, seconds=10.0,
              drain_seconds=5.0)
    a = Traffic(mix(name), seed=2**31 + 17, **kw)
    b = Traffic(mix(name), seed=2**31 + 17, **kw)
    c = Traffic(mix(name), seed=5, **kw)
    for k in (0, 3, 40):
        ra, rb, rc = a.request(k), b.request(k), c.request(k)
        assert np.array_equal(ra.prompt, rb.prompt) and ra.max_new == rb.max_new
        assert ra.due == rb.due
        assert not np.array_equal(ra.prompt[:16], rc.prompt[:16])
    # another seed: the same lengths in another order
    assert sorted(a.prompt_len) == sorted(c.prompt_len)
    assert sorted(a.output_len) == sorted(c.output_len)


def test_quantile_lengths_follow_the_distribution():
    q = quantiles({"dist": "lognormal", "median": 512, "sigma": 1.0,
                   "lo": 32, "hi": 4096}, 1001)
    assert q[500] == 512 and q.min() >= 32 and q.max() <= 4096
    u = quantiles({"dist": "uniform", "lo": 2048, "hi": 8192}, 4)
    assert list(u) == [2816, 4352, 5888, 7424]


def test_closed_loop_starts_staggered():
    kw = dict(slots=12, vocab=1000, max_len=10240, seconds=10.0)
    t = Traffic(mix("decode-long"), seed=3, **kw)
    wave = t.first_wave()
    assert len(wave) == 12
    left = sorted(r.max_new for r in wave)
    # budgets spread over the steady state's residual range: completions
    # start within the first hundred steps and keep coming
    assert left[0] < 100 and left[-1] > 1024
    assert all(b - a > 50 for a, b in zip(left, left[1:]))
    for i, r in enumerate(wave):
        assert len(r.prompt) + r.max_new == t.prompt_len[i] + t.output_len[i]
    # another seed: the same budgets and set-up work, in another order
    other = Traffic(mix("decode-long"), seed=4, **kw).first_wave()
    assert sorted(r.max_new for r in other) == left
    assert sum(len(r.prompt) for r in other) == sum(len(r.prompt) for r in wave)
    assert [r.max_new for r in other] != [r.max_new for r in wave]
    assert t.next_request().index == 12


def test_open_loop_window_holds_rate_times_seconds():
    t = Traffic(mix("chat-poisson"), slots=12, vocab=1000, max_len=10240,
                seed=9, seconds=10.0, drain_seconds=5.0)
    rate = mix("chat-poisson")["rate_per_s"]
    assert t.in_window == round(rate * 10)
    assert (np.diff(t.due) >= 0).all()
    assert t.due[t.in_window - 1] < 10.0 <= t.due[t.in_window]


def test_open_loop_seeds_reorder_one_schedule_locally():
    """Two seeds get the same arrivals and (prompt, output) pairs; each
    request moves only within its run of ``REORDER`` positions."""
    from bench.traffic import REORDER

    m = mix("chat-poisson")
    k = REORDER
    kw = dict(slots=26, vocab=1000, max_len=4608, seconds=45.0,
              drain_seconds=20.0)
    a = Traffic(m, seed=2**31 + 5, **kw)
    b = Traffic(m, seed=7, **kw)
    assert a.in_window == b.in_window and a.n == b.n
    starts = list(range(0, a.in_window, k)) + [a.in_window] + \
        list(range(a.in_window + k, a.n, k)) + [a.n]
    for lo, hi in zip(starts, starts[1:]):
        pa = sorted(zip(a.prompt_len[lo:hi], a.output_len[lo:hi]))
        pb = sorted(zip(b.prompt_len[lo:hi], b.output_len[lo:hi]))
        assert pa == pb
        assert a.due[hi - 1] == pytest.approx(b.due[hi - 1])
    assert not np.array_equal(a.prompt_len, b.prompt_len)
    assert not np.allclose(a.due, b.due)


class FakeResult:
    def __init__(self):
        self.status, self.tokens = "ok", []
        self.ttft_s = self.tpot_s = self.queue_wait_s = None


class FakeEngine:
    """Serves every request in ``steps`` steps of ``step_s`` seconds."""

    def __init__(self, step_s=0.004, steps=3):
        self.step_s, self.steps = step_s, steps
        self.live, self.res, self.t_sub, self.out = {}, {}, {}, 0

    def submit(self, req):
        self.live[req.uid] = [req, 0]
        self.t_sub[req.uid] = time.perf_counter()
        self.res[req.uid] = FakeResult()

    def step(self):
        time.sleep(self.step_s)
        now = time.perf_counter()
        for uid, st in list(self.live.items()):
            st[1] += 1
            self.out += 1
            if st[1] == 1:
                self.res[uid].ttft_s = now - self.t_sub[uid]
            if st[1] >= self.steps:
                r = self.res[uid]
                r.tokens = [1] * st[1]
                r.tpot_s = self.step_s
                del self.live[uid]
        return len(self.live)

    def results(self):
        return dict(self.res)

    def reset_metrics(self):
        self.out = 0

    def summary(self):
        return {"tokens_out": self.out}


def test_open_loop_submits_on_wall_clock_due_times():
    m = dict(mix("chat-poisson"), rate_per_s=40.0, drain_seconds=1.0)
    t = Traffic(m, slots=4, vocab=100, max_len=10240, seed=4, seconds=1.0,
                drain_seconds=1.0)
    drv = R.Driver(FakeEngine(), {"deployment": {"page_size": 128}}, cap=4,
                   account=False)
    t0, t_end, summary, late = R.run_window(drv, t, 1.0)
    assert t_end - t0 >= 1.0 and summary["tokens_out"] > 0
    window = [r for r in drv.reqs.values() if r["uid"] < t.in_window]
    assert len(window) == t.in_window
    for r in window:
        assert r["submitted"] >= r["due"]          # never early
    assert max(late) < 0.05                      # a step or two late at most
    assert all(r["done"] for r in window)        # drained after the window


def test_p95_counts_failed_requests_as_missing():
    ok = [0.1 * i for i in range(1, 20)]
    assert R.percentile(ok + [0.05], 95) == pytest.approx(1.8)
    assert R.percentile(ok + [math.inf], 95) == pytest.approx(1.9)
    assert R.percentile(ok + [math.inf, math.inf], 95) == math.inf
    assert R.percentile([], 95) == math.inf
