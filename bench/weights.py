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
scout the very same integer parts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one past 32 bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def dims(c: dict) -> dict:
    """The sizes of a configuration file under short names."""
    window = c.get("sliding_window") or 0
    if not c.get("use_sliding_window", True):
        window = 0
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"],
            "H": c["num_attention_heads"], "N": c["num_key_value_heads"],
            "hd": c["head_dim"], "f": c["intermediate_size"],
            "V": c["vocab_size"], "window": int(window),
            "tied": bool(c["tie_word_embeddings"]),
            "bias": bool(c.get("attention_bias", False)),
            "eps": float(c["rms_norm_eps"]),
            "rope_theta": float(c["rope_theta"])}


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


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
    return {"embed": embed, "layers": layers,
            "final_norm": {"w": jnp.ones((d,), dt)}}


_make_one = jax.jit(_make, static_argnums=(1,))


def _freeze(x):
    if isinstance(x, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in x.items()))
    if isinstance(x, list):
        return tuple(_freeze(v) for v in x)
    return x


def program_config(config: dict):
    """The program's ModelConfig for a configuration file: the registered
    model, with every size, the RoPE base, the window, the dtype and the
    HDP settings taken from the file (the file is what is run)."""
    from repro.configs import get_config
    from repro.core.config import HDPConfig

    D = dims(config)
    return get_config(config["model"]).replace(
        n_layers=D["L"], d_model=D["d"], n_heads=D["H"], n_kv_heads=D["N"],
        head_dim=D["hd"], d_ff=D["f"], vocab_size=D["V"],
        sliding_window=D["window"], tie_embeddings=D["tied"],
        qkv_bias=D["bias"], rope_theta=D["rope_theta"],
        dtype=config["torch_dtype"], hdp=HDPConfig(**config["hdp"]))


def tp_shardings(config: dict, mesh):
    """The program's tensor-parallel layout of ``config``'s weights on
    ``mesh``: a tree of NamedShardings."""
    from repro.distribution.sharding import RULES_TP, tree_specs
    from repro.models.registry import abstract_params

    return tree_specs(*abstract_params(program_config(config)), mesh, RULES_TP)


def make_params(config: dict, seed: int, mesh=None):
    """The weights of ``config`` (a configuration file's JSON object) for
    ``seed``, in the configuration's dtype, made in one jitted call: on
    the default device, or laid out on ``mesh`` as the program shards
    them (``tp_shardings``), with the same values."""
    if not jax.config.jax_threefry_partitionable:
        raise RuntimeError("the benchmark's weights are drawn with "
                           "jax_threefry_partitionable on")
    keep = {k: config[k] for k in (
        "num_hidden_layers", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size",
        "tie_word_embeddings", "attention_bias", "torch_dtype", "rms_norm_eps",
        "rope_theta", "sliding_window", "use_sliding_window", "weights")
        if k in config}
    frozen = tuple((k, _freeze(v) if k != "weights" else
                    tuple(sorted(v.items()))) for k, v in sorted(keep.items()))
    if mesh is None:
        return _make_one(seed_key(seed), frozen)
    make = jax.jit(_make, static_argnums=(1,),
                   out_shardings=tp_shardings(config, mesh))
    return make(seed_key(seed), frozen)
