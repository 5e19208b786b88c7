"""The per-layer metrics read from the engine's span totals and prompt
token counters (``step_host_ms.*``, ``prefill_pad_share.chat``): nothing
from a program without them, the right value from a hand-made summary
and from a tiny engine's own; and ``ttft_p95_s.chat`` from the open
loop's times to first token."""
import math

import numpy as np
import pytest

from bench.run import MetricContext, load_reader

STEP_HOST = ("step_host_ms.long", "step_host_ms.chat")
NEW = STEP_HOST + ("prefill_pad_share.chat",)


def ctx(summary):
    return MetricContext(None, summary, None, None, None, None, 1)


@pytest.mark.parametrize("name", NEW)
def test_reads_nothing_from_a_program_without_spans(name):
    """The summary of the engine before its spans and its prompt-token
    counter gives no value, and raises nothing."""
    old = {"prefill_s": 0.4, "decode_s": 7.0, "prefill_calls": 9,
           "prefill_tokens": 4096, "decode_steps": 220, "tokens_out": 2640}
    assert load_reader(name)(ctx(old)) is None
    assert load_reader(name)(ctx({})) is None


@pytest.mark.parametrize("name", STEP_HOST)
def test_step_host_ms_on_a_hand_made_summary(name):
    spans = {"engine.step": {"s": 3.5, "n": 110},
             "engine.decode": {"s": 3.0, "n": 100},
             "engine.decode.wait": {"s": 2.8, "n": 100},
             "engine.stats": {"s": 0.1, "n": 100}}
    read = load_reader(name)
    assert read(ctx({"spans": spans})) == \
        pytest.approx(1000.0 * (3.5 - 2.8 - 0.1) / 100)
    assert read(ctx({"spans": {"engine.step": {"s": 1.0, "n": 5}}})) is None


def test_prefill_pad_share_on_a_hand_made_summary():
    read = load_reader("prefill_pad_share.chat")
    assert read(ctx({"prefill_prompt_tokens": 300,
                     "prefill_tokens": 400})) == pytest.approx(25.0)
    assert read(ctx({"prefill_prompt_tokens": 0, "prefill_tokens": 0})) is None


def test_readers_on_a_tiny_engines_summary():
    """A tiny engine's summary after a few steps: the host time per
    decode step is positive and below the whole step's, and the padding
    share is the counters' own."""
    from repro.configs import get_config
    from repro.configs.base import reduced
    from repro.serving import Engine, Request

    eng = Engine(reduced(get_config("qwen2-1.5b")), max_batch=2, max_len=64,
                 prefill_buckets=(16, 32))
    rng = np.random.default_rng(0)
    for uid, n in enumerate((5, 20)):
        eng.submit(Request(uid=uid, prompt=rng.integers(1, 250, n).tolist(),
                           max_new_tokens=4))
    for _ in range(3):
        eng.step()
    s = eng.summary()
    spans = s["spans"]
    host = load_reader("step_host_ms.long")(ctx(s))
    per_step = 1000.0 * spans["engine.step"]["s"] / spans["engine.decode"]["n"]
    assert 0.0 < host < per_step
    assert load_reader("step_host_ms.chat")(ctx(s)) == pytest.approx(host)
    pad = load_reader("prefill_pad_share.chat")(ctx(s))
    assert s["prefill_prompt_tokens"] == 25
    assert pad == pytest.approx(
        100.0 * (1.0 - 25 / s["prefill_tokens"]))
    assert 0.0 < pad < 100.0


def test_ttft_p95_chat_is_the_nearest_rank_of_the_window():
    """The 95th percentile by nearest rank of the TTFTs the harness
    handed over, a failed request (+inf) among them; nothing from a
    closed loop, which hands over none."""
    read = load_reader("ttft_p95_s.chat")

    def of(ttfts):
        return read(MetricContext(None, {}, {"ttfts": ttfts}, None, None,
                                  None, 1))
    ttfts = [0.01 * (i + 1) for i in range(29)]
    assert of(ttfts) == pytest.approx(0.28)        # rank ceil(27.55) = 28
    assert of(ttfts[::-1]) == pytest.approx(0.28)
    assert of(ttfts[:19] + [math.inf]) == pytest.approx(0.19)
    assert of(ttfts[:10] + [math.inf] * 2) == math.inf
    assert of([]) is None
