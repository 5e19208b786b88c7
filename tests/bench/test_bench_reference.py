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


#: sha256 of the one-chip weights of ``data/tiny.json`` at seed 2**31 + 3
#: (every leaf's bytes in tree order), and of the reference's ``max``,
#: ``argmax`` and ``served`` over fixed tokens with its mean gap, as the
#: harness gave them before it could run four-chip cells
PINNED_WEIGHTS = {
    "float32": "2f218a2f87bcf88cc170333a083d10b5814896c88675bd4225c842ad55187e9c",
    "bfloat16": "4763c0599a5d690d0470951147dd3ea323fc77177c4acb5922851dbf747ace53",
}
PINNED_REFERENCE = {
    "fp32": (3.1112746588885782,
             "c95aa00ae36e87056724dc12c53bc52ab8b74b8c3d60535bae37b8587f2d8fb7"),
    "bf16": (3.1078906271606686,
             "11b93ff4da01699d1385e31dc3eb954913e0ba3de91e33fb58e29d30de311ec8"),
}


def _digest(arrays):
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def test_one_chip_weights_and_reference_are_bitwise_as_before():
    import json
    from pathlib import Path

    import jax
    import numpy as np

    from bench.reference.model import served_logits
    from bench.weights import make_params

    seed = 2**31 + 3
    cfg = json.loads((Path(__file__).parent / "data/tiny.json").read_text())
    for dtype, want in PINNED_WEIGHTS.items():
        params = make_params(dict(cfg, torch_dtype=dtype), seed)
        assert _digest(jax.tree.leaves(params)) == want, dtype
    params = make_params(cfg, seed)
    rng = np.random.default_rng(seed)
    prompt, served = rng.integers(1, 256, 300), rng.integers(1, 256, 40)
    for precision, (gap, want) in PINNED_REFERENCE.items():
        r = served_logits(cfg, params, prompt, served, precision=precision)
        assert float(np.mean(r["max"] - r["served"])) == gap, precision
        assert _digest(r[k] for k in ("max", "argmax", "served")) == want, precision
