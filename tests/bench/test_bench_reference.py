"""The plain reference against the engine, through the harness, on the
CPU at a tiny size: the engine's prefill + decode agree with it, and the
control (the reference one precision step down, put in the program's
place) comes out not correct."""


def test_engine_agrees_with_reference_and_control_fails(tiny_run):
    out = tiny_run(seed=2**31 + 3, control=True)
    gap = out["check"]["mean_gap"]
    limit = gap["limit"]
    prog = out["program"]
    print("program", prog, "control", gap["value"])
    assert prog["mean_gap"] <= limit and prog["tokens"] >= 20
    assert not out["correct"], out["check"]
    assert gap["value"] > 3 * limit
    assert out["check"]["tokens_compared"]["value"] == prog["tokens"]
    assert list(out)[-1] == "check"            # compared numbers come last
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}


def test_short_closed_window_drains_until_the_sample_is_full(tiny_run):
    # 3 engine steps finish none or one of the first wave's budgets; the
    # requests in flight are served on until the sample holds min_tokens
    out = tiny_run(seed=2**31 + 7, seconds=0.03)
    assert out["check"]["tokens_compared"]["value"] >= 20, out["check"]
    assert out["correct"], out["check"]
