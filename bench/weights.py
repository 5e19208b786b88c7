"""Seeded random weights for a configuration file, made on the device.

The benchmark makes its weights itself, so that the plain reference can
make the very same ones again from the seed without taking anything the
program under test produced. The tree has the layout the serving engine
takes (``{"embed", "layers", "final_norm"}``, layers stacked on a leading
axis); the harness checks that layout against the program's own before it
hands the tree over.

Given a mesh, the same tree is made in one jitted call whose outputs are
laid out as the program lays out its weights for tensor parallelism
(``RULES_TP``: heads, kv heads, the MLP width and the vocabulary on the
mesh's ``model`` axis, norms replicated), so that each chip holds its
share and its share of the float32 temporaries alone. Threefry's
partitionable mode makes every value bitwise the one the unsharded call
gives.

Which biases a model has follows its published architecture (the
configuration's ``architectures[0]``, ``BIASES``): Qwen2's q/k/v biases,
always, or Llama's q/k/v and o biases under ``attention_bias`` and
gate/up/down biases under ``mlp_bias``. The leaves beyond q/k/v are ``layers.attn.bo``
``[L, d]``, ``layers.ffn.b_gate`` and ``layers.ffn.b_up`` ``[L, f]`` and
``layers.ffn.b_down`` ``[L, d]``, drawn from keys after every key of the
other leaves, so a model without them draws what it drew before.

Every matrix is N(0, 1/fan_in). The configuration's ``weights`` group adds
what a trained model has and a plain draw lacks: ``qk_scale`` multiplies
the q and k projections and ``qk_bias_std`` / ``v_bias_std`` draw the
q/k and v biases (models with them), so that queries and keys have
integer parts that HDP's integer scout can see (with N(0, 1/fan_in) alone
most entries of q and k lie in (-1, 1), their integer parts are 0, and
the head gate prunes nearly every head). A ``qk_scale`` of 0 makes the
queries and keys their biases alone, rotated by position: attention and
HDP's masks then depend on position only, and the engine and the plain
reference (which holds rotated queries and keys in the served dtype too)
scout the very same integer parts. ``o_bias_std`` and ``mlp_bias_std``
draw the o and MLP biases; each is required where its biases exist.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding


#: the biases of each published architecture, keyed by the configuration's
#: ``architectures[0]``: ``qkv`` (q, k and v projections), ``o`` (output
#: projection) and ``mlp`` (gate, up and down projections), each with the
#: configuration key that switches it on, or True where the architecture
#: fixes it
BIASES = {
    # transformers 4.57 models/qwen2/modeling_qwen2.py:134-137 (q, k and v
    # always biased, o not) and :40-42 (no MLP bias)
    "Qwen2ForCausalLM": {"qkv": True},
    # models/llama/modeling_llama.py:211-220 (q, k, v and o under
    # attention_bias) and :149-151 (gate, up and down under mlp_bias)
    "LlamaForCausalLM": {"qkv": "attention_bias", "o": "attention_bias",
                         "mlp": "mlp_bias"},
}
#: logical axes (``repro.distribution.sharding``) of the biases beyond
#: q/k/v, for their tensor-parallel layout while the program's ModelConfig
#: has no field for them (``tp_shardings``): the MLP width on ``model``
EXTRA_BIAS_AXES = {
    ("attn", "bo"): ("layers", "embed"),
    ("ffn", "b_gate"): ("layers", "mlp"),
    ("ffn", "b_up"): ("layers", "mlp"),
    ("ffn", "b_down"): ("layers", "embed"),
}


class ProgramLacks(ValueError):
    """The program's ModelConfig has no field for what the configuration
    file states, so it cannot serve that model."""


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one past 32 bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _biases(c: dict) -> dict:
    """``{"qkv", "o", "mlp"} -> bool``: which biases the configuration's
    published architecture puts where (``BIASES``)."""
    arch = (c.get("architectures") or [None])[0]
    if arch not in BIASES:
        raise ValueError(f"architecture {arch!r}: the benchmark knows the "
                         f"biases of {sorted(BIASES)} only")
    keys = BIASES[arch]

    def on(key):
        return key is True or bool(c.get(key, False))

    return {part: part in keys and on(keys[part])
            for part in ("qkv", "o", "mlp")}


def dims(c: dict) -> dict:
    """The sizes of a configuration file under short names."""
    b = _biases(c)
    window = c.get("sliding_window") or 0
    if not c.get("use_sliding_window", True):
        window = 0
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"],
            "H": c["num_attention_heads"], "N": c["num_key_value_heads"],
            "hd": c["head_dim"], "f": c["intermediate_size"],
            "V": c["vocab_size"], "window": int(window),
            "tied": bool(c["tie_word_embeddings"]),
            "bias": b["qkv"], "o_bias": b["o"], "mlp_bias": b["mlp"],
            "eps": float(c["rms_norm_eps"]),
            "rope_theta": float(c["rope_theta"])}


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _std(w: dict, name: str) -> float:
    if name not in w:
        raise ValueError(f"the configuration's weights group has no "
                         f"{name!r}, which its published biases need")
    return float(w[name])


def _make(key, frozen):
    c = dict(frozen)
    w = dict(c.pop("weights"))
    D = dims(c)
    dt = jnp.dtype(c["torch_dtype"])
    L, d, H, N, hd, f, V = (D[k] for k in ("L", "d", "H", "N", "hd", "f", "V"))
    ks = iter(jax.random.split(key, 16))
    qk = float(w["qk_scale"])
    layers = {
        "attn": {
            "wq": _normal(next(ks), (L, d, H, hd), qk / d ** 0.5, dt),
            "wk": _normal(next(ks), (L, d, N, hd), qk / d ** 0.5, dt),
            "wv": _normal(next(ks), (L, d, N, hd), 1 / d ** 0.5, dt),
            "wo": _normal(next(ks), (L, H, hd, d), 1 / (H * hd) ** 0.5, dt),
        },
        "ln1": {"w": jnp.ones((L, d), dt)},
        "ln2": {"w": jnp.ones((L, d), dt)},
        "ffn": {
            "w_gate": _normal(next(ks), (L, d, f), 1 / d ** 0.5, dt),
            "w_up": _normal(next(ks), (L, d, f), 1 / d ** 0.5, dt),
            "w_down": _normal(next(ks), (L, f, d), 1 / f ** 0.5, dt),
        },
    }
    if D["bias"]:
        b, bv = float(w["qk_bias_std"]), float(w["v_bias_std"])
        layers["attn"].update(bq=_normal(next(ks), (L, H, hd), b, dt),
                              bk=_normal(next(ks), (L, N, hd), b, dt),
                              bv=_normal(next(ks), (L, N, hd), bv, dt))
    embed = {"tok": _normal(next(ks), (V, d), 1 / d ** 0.5, dt)}
    if not D["tied"]:
        embed["lm_head"] = _normal(next(ks), (d, V), 1 / d ** 0.5, dt)
    if D["o_bias"]:
        layers["attn"]["bo"] = _normal(next(ks), (L, d),
                                       _std(w, "o_bias_std"), dt)
    if D["mlp_bias"]:
        b = _std(w, "mlp_bias_std")
        layers["ffn"].update(b_gate=_normal(next(ks), (L, f), b, dt),
                             b_up=_normal(next(ks), (L, f), b, dt),
                             b_down=_normal(next(ks), (L, d), b, dt))
    return {"embed": embed, "layers": layers,
            "final_norm": {"w": jnp.ones((d,), dt)}}


_make_one = jax.jit(_make, static_argnums=(1,))


def _freeze(x):
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    if isinstance(x, list):
        return tuple(_freeze(v) for v in x)
    return x


def _program_base(config: dict):
    """The program's ModelConfig of ``config`` with q/k/v biases at most."""
    from repro.configs import get_config
    from repro.core.config import HDPConfig

    D = dims(config)
    return get_config(config["model"]).replace(
        n_layers=D["L"], d_model=D["d"], n_heads=D["H"], n_kv_heads=D["N"],
        head_dim=D["hd"], d_ff=D["f"], vocab_size=D["V"],
        sliding_window=D["window"], tie_embeddings=D["tied"],
        qkv_bias=D["bias"], rope_theta=D["rope_theta"],
        dtype=config["torch_dtype"], hdp=HDPConfig(**config["hdp"]))


def program_config(config: dict):
    """The program's ModelConfig for a configuration file: the registered
    model, with every size, the RoPE base, the window, the dtype, the
    biases and the HDP settings taken from the file (the file is what is
    run). ``o_bias`` / ``mlp_bias`` are passed only where the file's
    architecture has those biases; raises ``ProgramLacks`` naming each
    such field the program's ModelConfig does not have."""
    cfg = _program_base(config)
    D = dims(config)
    want = {k: True for k in ("o_bias", "mlp_bias") if D[k]}
    have = {f.name for f in dataclasses.fields(cfg)}
    missing = [k for k in want if k not in have]
    if missing:
        raise ProgramLacks(f"the program's ModelConfig has no field "
                           f"{', '.join(map(repr, missing))}: it cannot serve "
                           f"{config['model']} with its published biases")
    return dataclasses.replace(cfg, **want)


def tp_shardings(config: dict, mesh):
    """The program's tensor-parallel layout of ``config``'s weights on
    ``mesh``: a tree of NamedShardings, from the program's own parameters
    of ``program_config(config)``. Where the program lacks the biases
    beyond q/k/v (``ProgramLacks``), those take the program's rules
    (``RULES_TP``) on their ``EXTRA_BIAS_AXES``, so that the weights can
    still be made and checked."""
    from repro.distribution.sharding import RULES_TP, spec_for, tree_specs
    from repro.models.registry import abstract_params

    try:
        return tree_specs(*abstract_params(program_config(config)), mesh,
                          RULES_TP)
    except ProgramLacks:
        pass
    out = tree_specs(*abstract_params(_program_base(config)), mesh, RULES_TP)
    frozen = _frozen(config)
    shapes = jax.eval_shape(lambda key: _make(key, frozen), seed_key(0))
    for (group, leaf), axes in EXTRA_BIAS_AXES.items():
        if leaf in shapes["layers"][group]:
            shape = shapes["layers"][group][leaf].shape
            out["layers"][group][leaf] = NamedSharding(
                mesh, spec_for(axes, shape, mesh, RULES_TP))
    return out


def _frozen(config: dict) -> tuple:
    """What the draw reads of ``config``, hashable."""
    keep = {k: config[k] for k in (
        "architectures", "num_hidden_layers", "hidden_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "vocab_size", "tie_word_embeddings",
        "attention_bias", "mlp_bias", "torch_dtype", "rms_norm_eps",
        "rope_theta", "sliding_window", "use_sliding_window", "weights")
        if k in config}
    return tuple((k, _freeze(v) if k != "weights" else
                  tuple(sorted(v.items()))) for k, v in sorted(keep.items()))


def make_params(config: dict, seed: int, mesh=None):
    """The weights of ``config`` (a configuration file's JSON object) for
    ``seed``, in the configuration's dtype, made in one jitted call: on
    the default device, or laid out on ``mesh`` as the program shards
    them (``tp_shardings``), with the same values."""
    if not jax.config.jax_threefry_partitionable:
        raise RuntimeError("the benchmark's weights are drawn with "
                           "jax_threefry_partitionable on")
    frozen = _frozen(config)
    if mesh is None:
        return _make_one(seed_key(seed), frozen)
    make = jax.jit(_make, static_argnums=(1,),
                   out_shardings=tp_shardings(config, mesh))
    return make(seed_key(seed), frozen)
