"""Llama's published block in the harness: the o-projection and MLP
biases that ``attention_bias`` / ``mlp_bias`` switch on are drawn in the
seeded weights and added by the plain reference where the modelling code
adds them (``data/tiny_llama.json``: 8 q / 4 kv heads, both keys set)."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights as W
from bench.reference.model import post_attention

DATA = Path(__file__).parent / "data"
SEED = 2**31 + 9
#: leaf -> (shape by dims, std key) of the biases beyond q/k/v
NEW = {("attn", "bo"): (("L", "d"), "o_bias_std"),
       ("ffn", "b_gate"): (("L", "f"), "mlp_bias_std"),
       ("ffn", "b_up"): (("L", "f"), "mlp_bias_std"),
       ("ffn", "b_down"): (("L", "d"), "mlp_bias_std")}


def llama():
    return json.loads((DATA / "tiny_llama.json").read_text())


def flat(params):
    return {jax.tree_util.keystr(k): np.asarray(v)
            for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}


def test_new_biases_are_drawn_at_their_shapes_and_stds():
    c = llama()
    D = W.dims(c)
    assert (D["bias"], D["o_bias"], D["mlp_bias"]) == (True, True, True)
    p = W.make_params(c, SEED)
    for (group, leaf), (shape, std) in NEW.items():
        x = np.asarray(p["layers"][group][leaf])
        assert x.shape == tuple(D[k] for k in shape), leaf
        want = c["weights"][std]
        assert abs(x.std() / want - 1) < 0.25, (leaf, x.std(), want)
    # a std scales its draw exactly, and nothing else
    twice = dict(c, weights=dict(c["weights"], o_bias_std=1.0,
                                 mlp_bias_std=1.0))
    a, b = flat(p), flat(W.make_params(twice, SEED))
    for k in a:
        scaled = any(f"['{leaf}']" in k for _, leaf in NEW)
        assert np.array_equal(b[k], 2 * a[k] if scaled else a[k]), k


@pytest.mark.parametrize("bias_keys, absent", [
    ({"mlp_bias": False}, ["b_gate", "b_up", "b_down"]),
    ({"architectures": ["Qwen2ForCausalLM"]}, ["bo", "b_gate", "b_up", "b_down"]),
])
def test_new_leaves_leave_every_other_leaf_as_it_was(bias_keys, absent):
    full = flat(W.make_params(llama(), SEED))
    part = flat(W.make_params(dict(llama(), **bias_keys), SEED))
    gone = {k for k in full if any(f"['{leaf}']" in k for leaf in absent)}
    assert len(gone) == len(absent) and set(part) == set(full) - gone
    for k, v in part.items():
        assert v.tobytes() == full[k].tobytes(), k


def test_an_unknown_architecture_is_refused():
    with pytest.raises(ValueError, match="MistralForCausalLM"):
        W.dims(dict(llama(), architectures=["MistralForCausalLM"]))
    c = llama()
    del c["architectures"]
    with pytest.raises(ValueError, match="architecture None"):
        W.make_params(c, SEED)


@pytest.mark.parametrize("std", ["o_bias_std", "mlp_bias_std"])
def test_a_missing_bias_std_is_refused(std):
    c = llama()
    del c["weights"][std]
    with pytest.raises(ValueError, match=std):
        W.make_params(c, SEED)


def test_program_config_passes_only_the_biases_the_file_has(monkeypatch):
    @dataclasses.dataclass(frozen=True)
    class Lacks:
        qkv_bias: bool = False

    @dataclasses.dataclass(frozen=True)
    class Has(Lacks):
        o_bias: bool = False
        mlp_bias: bool = False

    monkeypatch.setattr(W, "_program_base", lambda c: Lacks())
    with pytest.raises(W.ProgramLacks, match="'o_bias', 'mlp_bias'"):
        W.program_config(llama())
    with pytest.raises(W.ProgramLacks, match="field 'o_bias': "):
        W.program_config(dict(llama(), mlp_bias=False))
    qwen = json.loads((DATA / "tiny.json").read_text())
    assert W.program_config(qwen) == Lacks()          # no field passed
    monkeypatch.setattr(W, "_program_base", lambda c: Has())
    assert W.program_config(llama()) == Has(o_bias=True, mlp_bias=True)
    assert W.program_config(dict(llama(), mlp_bias=False)) == Has(o_bias=True)



@pytest.mark.parametrize("attention_bias", [False, None])
def test_qwen2_draws_its_qkv_biases_whatever_attention_bias_says(
        attention_bias):
    """Qwen2's architecture fixes its q/k/v biases: a file that leaves
    ``attention_bias`` out, as Qwen2's published config does, or sets it
    false draws the same tree."""
    qwen = json.loads((DATA / "tiny.json").read_text())
    other = dict(qwen, attention_bias=attention_bias)
    if attention_bias is None:
        del other["attention_bias"]
    assert W.dims(other)["bias"] and not W.dims(other)["o_bias"]
    a, b = flat(W.make_params(qwen, SEED)), flat(W.make_params(other, SEED))
    assert set(a) == set(b) and any("['bq']" in k for k in a)
    for k in a:
        assert a[k].tobytes() == b[k].tobytes(), k


def test_tp_shardings_take_the_programs_layout_once_it_has_the_fields(
        monkeypatch):
    """Where the program's ModelConfig takes the file's biases, the layout
    is the program's own for that config, with no table of the
    benchmark's beside it."""
    import repro.distribution.sharding as S
    import repro.models.registry as R

    has, seen = object(), []
    monkeypatch.setattr(W, "program_config", lambda c: has)
    monkeypatch.setattr(R, "abstract_params",
                        lambda cfg: seen.append(cfg) or ("shapes", "axes"))
    monkeypatch.setattr(S, "tree_specs",
                        lambda shapes, axes, mesh, rules: (shapes, axes, mesh,
                                                           rules))
    assert W.tp_shardings(llama(), "mesh") == ("shapes", "axes", "mesh",
                                               S.RULES_TP)
    assert seen == [has]

def _normal(rng, mean, std, shape):
    return rng.normal(mean, std, shape).astype(np.float32)


def _layer(rng, d, f, ho, biased):
    """One layer's post-attention weights, float32 numpy."""
    lp = {"attn": {"wo": _normal(rng, 0, 0.2, (ho, d))},
          "ln2": {"w": _normal(rng, 1, 0.1, (d,))},
          "ffn": {"w_gate": _normal(rng, 0, 0.2, (d, f)),
                  "w_up": _normal(rng, 0, 0.2, (d, f)),
                  "w_down": _normal(rng, 0, 0.2, (f, d))}}
    if biased:
        lp["attn"]["bo"] = _normal(rng, 0, 0.5, (d,))
        lp["ffn"].update(b_gate=_normal(rng, 0, 0.5, (f,)),
                         b_up=_normal(rng, 0, 0.5, (f,)),
                         b_down=_normal(rng, 0, 0.5, (d,)))
    return lp


def test_post_attention_adds_each_bias_where_the_modelling_code_does():
    rng = np.random.default_rng(3)
    S, d, f, ho, eps = 5, 16, 24, 32, 1e-6
    lp = _layer(rng, d, f, ho, biased=True)
    x, o = _normal(rng, 0, 1, (S, d)), _normal(rng, 0, 1, (S, ho))
    with jax.default_matmul_precision("highest"):
        got = post_attention(jax.tree.map(jnp.asarray, lp), jnp.asarray(x),
                             jnp.asarray(o), eps)
    lp, x, o = jax.tree.map(lambda v: v.astype(np.float64), (lp, x, o))
    a, m = lp["attn"], lp["ffn"]
    # LlamaAttention.o_proj, the residual, RMSNorm, LlamaMLP, the residual
    x1 = x + o @ a["wo"] + a["bo"]
    h = x1 / np.sqrt((x1 * x1).mean(-1, keepdims=True) + eps) * lp["ln2"]["w"]
    g = h @ m["w_gate"] + m["b_gate"]
    want = x1 + ((g / (1 + np.exp(-g))) * (h @ m["w_up"] + m["b_up"])
                 @ m["w_down"] + m["b_down"])
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=2e-5, atol=2e-5)


def test_zero_biases_give_bitwise_the_unbiased_layer():
    rng = np.random.default_rng(4)
    S, d, f, ho, eps = 5, 16, 24, 32, 1e-6
    plain = jax.tree.map(jnp.asarray, _layer(rng, d, f, ho, biased=False))
    zero = jax.tree.map(lambda v: v, plain)
    zero["attn"]["bo"] = jnp.zeros((d,), jnp.float32)
    zero["ffn"].update(b_gate=jnp.zeros((f,), jnp.float32),
                       b_up=jnp.zeros((f,), jnp.float32),
                       b_down=jnp.zeros((d,), jnp.float32))
    x = jnp.asarray(_normal(rng, 0, 1, (S, d)))
    o = jnp.asarray(_normal(rng, 0, 1, (S, ho)))
    for precision in ("fp32", "bf16"):
        a = post_attention(plain, x, o, eps, precision)
        b = post_attention(zero, x, o, eps, precision)
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), precision


def test_llama_block_engine_agrees_with_reference(monkeypatch):
    """The engine serving ``tiny_llama.json`` through the whole harness:
    correct, and not correct with a served token altered. Skipped, with
    the fields named, while the program's ModelConfig cannot take the
    file's biases."""
    from conftest import tiny_runner
    from test_bench_faults import altered_token

    try:
        W.program_config(llama())
    except W.ProgramLacks as e:
        pytest.skip(str(e))
    go = tiny_runner(monkeypatch, "tiny_llama.json")
    out = go(seed=2**31 + 13)
    assert out["correct"], out["check"]
    assert out["check"]["tokens_compared"]["value"] >= 20
    altered_token(monkeypatch)
    out = go(seed=2**31 + 13)
    assert not out["correct"], out["check"]
