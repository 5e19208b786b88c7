"""Engine spans and named scopes: the host phases of ``Engine.step`` as
``engine.*`` spans (profiler annotations plus the recorder behind
``prefill_s``/``decode_s``), the HDP stages as named scopes in the
compiled programs, and the real-vs-padded prefill token counters."""
from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import reduced
from repro.serving import Engine, Request
from repro.serving.tracing import SpanRecorder


def _engine(**kw):
    cfg = reduced(get_config("qwen2-1.5b"))
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("prefill_buckets", (16, 32))
    return Engine(cfg, **kw)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, size=n).tolist() for n in lengths]


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith("engine.")]
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_recorder_totals_and_reset():
    rec = SpanRecorder()
    with rec.span("a") as sp:
        with rec.span("a.b"):
            pass
    with rec.span("a"):
        pass
    tot = rec.totals()
    assert tot["a"]["n"] == 2 and tot["a.b"]["n"] == 1
    assert tot["a"]["s"] >= sp.s >= tot["a.b"]["s"] >= 0.0
    assert rec.total("a") == tot["a"]["s"] and rec.total("none") == 0.0
    with pytest.raises(ValueError):
        with rec.span("c"):
            raise ValueError("the span still closes")
    assert rec.totals()["c"]["n"] == 1
    rec.reset()
    assert rec.totals() == {}


def test_one_step_emits_the_engine_spans_in_their_nesting(tmp_path):
    """Profiled on the CPU, one step that admits a request and decodes
    shows every engine span in the profiler's host plane, nested as
    step > admit > prefill (> stats)/activate and step > decode >
    dispatch/wait, then stats and drain after the decode."""
    eng = _engine(collect_stats=True)
    a, b = _prompts([10, 12])
    eng.submit(Request(0, a, max_new_tokens=3))
    eng.run()                                    # compile outside the trace
    eng.submit(Request(1, b, max_new_tokens=3))
    jax.profiler.start_trace(str(tmp_path))
    eng.step()
    jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path))
    by = {}
    for e in ev:
        by.setdefault(e[0], []).append(e)
    step, = by["engine.step"]
    admit, = by["engine.admit"]
    decode, = by["engine.decode"]
    assert _inside(admit, step) and _inside(decode, step)
    assert by["engine.prefill"] and by["engine.activate"]
    assert all(_inside(e, admit)
               for e in by["engine.prefill"] + by["engine.activate"])
    assert admit[2] <= decode[1]
    dispatch, = by["engine.decode.dispatch"]
    wait, = by["engine.decode.wait"]
    assert _inside(dispatch, decode) and _inside(wait, decode)
    assert dispatch[2] <= wait[1]
    # a stats sample per program: the prefill's inside its span, the
    # decode's after the decode
    pre, = [e for e in by["engine.stats"] if e[1] < decode[1]]
    stats, = [e for e in by["engine.stats"] if e[1] >= decode[2]]
    assert any(_inside(pre, e) for e in by["engine.prefill"])
    drain, = by["engine.drain"]
    assert _inside(stats, step) and _inside(drain, step)
    assert decode[2] <= stats[1] <= stats[2] <= drain[1]


def test_timers_are_the_span_totals():
    """``prefill_s``/``decode_s`` are the recorder's ``engine.prefill`` /
    ``engine.decode`` totals, a request's ``prefill_s`` is its share of
    its prefill span, and ``reset_metrics()`` zeroes both."""
    eng = _engine()
    for uid, p in enumerate(_prompts([10, 20, 40])):   # one chunked
        eng.submit(Request(uid, p, max_new_tokens=4))
    res = eng.run()
    s = eng.summary()
    spans = s["spans"]
    assert s["prefill_s"] == spans["engine.prefill"]["s"] > 0
    assert s["decode_s"] == spans["engine.decode"]["s"] > 0
    assert spans["engine.decode"]["n"] == spans["engine.decode.wait"]["n"]
    assert spans["engine.step"]["n"] >= spans["engine.decode"]["n"]
    assert sum(r.prefill_s for r in res.values()) == pytest.approx(
        spans["engine.prefill"]["s"])
    assert s["decode_tok_s"] == pytest.approx(s["tokens_out"] / s["decode_s"])
    eng.reset_metrics()
    s = eng.summary()
    assert s["spans"] == {} and s["prefill_s"] == 0.0 and s["decode_s"] == 0.0
    assert s["prefill_prompt_tokens"] == 0 and s["prefill_tokens"] == 0


@pytest.mark.parametrize("lengths,padded", [
    ([16, 32], 16 + 32),                 # bucket-sized: nothing padded
    ([5, 20], 16 + 32),
    ([40], 32 + 16),                     # a 32-token chunk and a 16 tail
])
def test_prefill_prompt_tokens_count_the_real_tokens(lengths, padded):
    eng = _engine(max_batch=1)
    for uid, p in enumerate(_prompts(lengths)):
        eng.submit(Request(uid, p, max_new_tokens=2))
    eng.run()
    m = eng.metrics
    assert m["prefill_prompt_tokens"] == sum(lengths)
    assert m["prefill_tokens"] == padded >= m["prefill_prompt_tokens"]


def test_decode_program_carries_the_named_scopes():
    """The compiled decode program's ops name HDP's stages and the
    layer's parts in their op_name metadata (named scopes write metadata
    only)."""
    eng = _engine()
    eng.submit(Request(0, _prompts([10])[0], max_new_tokens=3))
    eng._admit()
    p = eng.pages
    hlo = eng._decode_jit.lower(
        1, eng._attn_epoch, eng.params, eng._last_tok, p.cache, p.table(),
        eng._floor_dev, eng._pos, eng._active_dev, eng._remaining_dev,
        eng._eos_dev, eng._zero_inject).compile().as_text()
    for scope in ("hdp.scout", "hdp.attend", "kv.write", "attn.proj", "mlp",
                  "lm_head"):
        assert f"/{scope}/" in hlo, scope
