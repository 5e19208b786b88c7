"""Decoder-only transformer LM (dense and MoE families).

Covers olmoe, llama4-scout, chameleon, nemotron-4, h2o-danube, qwen2,
granite: GQA, RoPE, qk-norm, QKV bias, SWA, squared-ReLU / GLU / GELU MLPs,
MoE with shared experts — all driven by ModelConfig. Layers are stacked and
scanned (small HLO, fast compiles, remat-able).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distribution.sharding import shard_activation as shd
from repro.models import layers as L
from repro.models import moe as M
from repro.models.attention import attn_apply, attn_init

F32 = jnp.float32


def _layer_init(cfg, rng, dtype) -> Tuple[Dict, Dict]:
    attn_p, attn_s = attn_init(cfg, L.key_for(rng, "attn"), dtype)
    ln1_p, ln1_s = L.norm_init(cfg, dtype)
    ln2_p, ln2_s = L.norm_init(cfg, dtype)
    if cfg.n_experts:
        ffn_p, ffn_s = M.moe_init(cfg, L.key_for(rng, "moe"), dtype)
    else:
        ffn_p, ffn_s = L.mlp_init(cfg, L.key_for(rng, "mlp"), dtype)
    return ({"attn": attn_p, "ln1": ln1_p, "ln2": ln2_p, "ffn": ffn_p},
            {"attn": attn_s, "ln1": ln1_s, "ln2": ln2_s, "ffn": ffn_s})


def init_params(cfg, rng) -> Tuple[Dict, Dict]:
    dtype = jnp.dtype(cfg.dtype)
    emb_p, emb_s = L.embed_init(cfg, L.key_for(rng, "embed"), dtype)
    keys = jax.random.split(L.key_for(rng, "layers"), cfg.n_layers)
    layers_p = jax.vmap(lambda k: _layer_init(cfg, k, dtype)[0])(keys)
    _, layer_s = _layer_init(cfg, keys[0], dtype)
    layers_s = jax.tree.map(
        lambda ax: ("layers",) + tuple(ax),
        layer_s, is_leaf=lambda x: isinstance(x, tuple))
    fin_p, fin_s = L.norm_init(cfg, dtype)
    return ({"embed": emb_p, "layers": layers_p, "final_norm": fin_p},
            {"embed": emb_s, "layers": layers_s, "final_norm": fin_s})


def _block(cfg, lp, x, *, mode, positions, cache, collect_stats,
           page_table=None, write_floor=None, attn=None, draft=None):
    h = L.apply_norm(cfg, lp["ln1"], x)
    a, new_cache, stats = attn_apply(
        cfg, lp["attn"], h, mode=mode, positions=positions, cache=cache,
        collect_stats=collect_stats, page_table=page_table,
        write_floor=write_floor, attn=attn, draft=draft)
    x = x + a
    h = L.apply_norm(cfg, lp["ln2"], x)
    with jax.named_scope("mlp"):
        if cfg.n_experts:
            m, aux = M.moe_apply(cfg, lp["ffn"], h)
        else:
            m, aux = L.mlp_apply(cfg, lp["ffn"], h), jnp.zeros((), F32)
    return x + m, new_cache, stats, aux


def _stack(cfg, params, x, *, mode, positions, cache, collect_stats,
           page_table=None, write_floor=None, attn=None, draft=None):
    """lax.scan over stacked layers; returns (x, new_cache, stats, aux).

    The KV cache rides in the scan CARRY with per-layer in-place
    dynamic-update-slice — emitting it as stacked scan outputs (`ys`)
    allocates a second full cache buffer that donation cannot alias
    (2-3 cache copies live at a 32k decode step)."""
    has_cache = cache is not None

    if not has_cache:
        def body(carry, lp):
            y, _, st, aux = _block(cfg, lp, carry, mode=mode,
                                   positions=positions, cache=None,
                                   collect_stats=collect_stats, attn=attn)
            return y, (st, aux)

        if cfg.remat:
            body = jax.checkpoint(body)
        x, (stats, aux) = jax.lax.scan(body, x, params["layers"])
        return x, None, stats, aux

    # the paged pool stays layer-stacked: attention writes and reads it
    # in place at the carried layer index, so no per-layer slice of the
    # pool is copied out and back (the Pallas decode kernel DMAs only
    # surviving pages straight from the stack)
    paged = page_table is not None and "k_pages" in cache

    def body(carry, lp):
        y, cache_all, li = carry
        if paged:
            lc = {**cache_all, "layer": li}
        else:
            lc = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, li, 0,
                                                       keepdims=False),
                cache_all)
        y, nc, st, aux = _block(cfg, lp, y, mode=mode, positions=positions,
                                cache=lc, collect_stats=collect_stats,
                                page_table=page_table,
                                write_floor=write_floor, attn=attn,
                                draft=draft)
        if paged:
            cache_all = {k: nc[k] for k in cache_all}
        else:
            cache_all = jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(
                    c, n.astype(c.dtype), li, 0),
                cache_all, nc)
        return (y, cache_all, li + 1), (st, aux)

    # no remat here: the cache path is inference-only (no backward), and
    # jax.checkpoint barriers force the carried cache to be COPIED twice
    # per layer (measured +160 ms memory_t at 32k decode)
    (x, new_cache, _), (stats, aux) = jax.lax.scan(
        body, (x, cache, jnp.asarray(0, jnp.int32)), params["layers"])
    return x, new_cache, stats, aux


def _embed_in(cfg, params, tokens):
    x = L.embed_tokens(params["embed"], tokens, cfg.d_model)
    if cfg.pos_emb == "sinusoidal":
        x = x + L.sinusoidal_pos(tokens.shape[1], cfg.d_model).astype(x.dtype)
    return shd(x, "batch", "seq_act", "embed_act")


def apply_train(cfg, params, batch, *, collect_stats: bool = False):
    tokens = batch["tokens"]
    x = _embed_in(cfg, params, tokens)
    positions = jnp.arange(tokens.shape[1])
    x, _, stats, aux = _stack(cfg, params, x, mode="train",
                              positions=positions, cache=None,
                              collect_stats=collect_stats)
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = L.lm_logits_sharded(params["embed"], x)
    return logits, {"aux_loss": aux.sum(), "hdp": stats}


def init_cache(cfg, batch: int, max_len: int, dtype=None) -> Dict:
    dt = jnp.dtype(dtype or cfg.dtype)
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def cache_specs(cfg) -> Dict:
    ax = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    return {"k": ax, "v": ax}


def apply_prefill(cfg, params, batch, cache, *, collect_stats: bool = False,
                  pos_offset=0, attn=None):
    """Run the prompt; fills cache, returns last-position logits.

    pos_offset (scalar, may be traced): absolute position of tokens[:, 0] —
    nonzero for chunked prefill, where each chunk appends to the cache
    behind the previous ones."""
    tokens = batch["tokens"]
    x = _embed_in(cfg, params, tokens)
    positions = pos_offset + jnp.arange(tokens.shape[1])
    x, new_cache, stats, _ = _stack(cfg, params, x, mode="prefill",
                                    positions=positions, cache=cache,
                                    collect_stats=collect_stats, attn=attn)
    with jax.named_scope("lm_head"):
        x = L.apply_norm(cfg, params["final_norm"], x[:, -1:])
        logits = L.lm_logits_sharded(params["embed"], x)
    return logits, new_cache, stats


def apply_decode(cfg, params, token, cache, pos, *, collect_stats: bool = False,
                 page_table=None, write_floor=None, attn=None, draft=None):
    """One decode step. token [B,1]; pos scalar int32 (aligned batch).

    page_table [B, nP] routes the step through the block-paged serving
    cache ({"k_pages","v_pages"[,"k_scout"]} leaves) instead of the dense
    contiguous layout. write_floor [B] fences each slot's shared
    read-only prefix pages from the K/V write (see attn_apply).

    token [B, S] with S > 1 is the speculative multi-query *verify*
    shape: ``pos`` must then be [B, S] consecutive positions per slot —
    all S rows are scored against the cache in one call, with per-row
    scout semantics identical to S sequential steps. draft: DraftProfile
    marking a self-speculative draft step (approximate attention)."""
    x = L.embed_tokens(params["embed"], token, cfg.d_model)
    if cfg.pos_emb == "sinusoidal":
        x = x + L.sinusoidal_pos(token.shape[1], cfg.d_model,
                                 offset=pos).astype(x.dtype)
    positions = pos[None] if jnp.ndim(pos) == 0 else pos
    x, new_cache, stats, _ = _stack(cfg, params, x, mode="decode",
                                    positions=positions, cache=cache,
                                    collect_stats=collect_stats,
                                    page_table=page_table,
                                    write_floor=write_floor, attn=attn,
                                    draft=draft)
    with jax.named_scope("lm_head"):
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = L.lm_logits(params["embed"], x)
    return logits, new_cache, stats


def param_count(cfg) -> int:
    d, f, v, hd = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.hd
    attn = d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd \
        + cfg.n_heads * hd * d
    if cfg.n_experts:
        ffn = cfg.n_experts * 3 * d * f + d * cfg.n_experts
        if cfg.n_shared_experts:
            ffn += 3 * d * f * cfg.n_shared_experts
    else:
        ffn = (3 if cfg.act == "silu_glu" else 2) * d * f
    per_layer = attn + ffn + 2 * d
    emb = v * d * (1 if cfg.tie_embeddings else 2)
    return cfg.n_layers * per_layer + emb + d


def active_param_count(cfg) -> int:
    if not cfg.n_experts:
        return param_count(cfg)
    d, f = cfg.d_model, cfg.d_ff
    attn = d * cfg.n_heads * cfg.hd + 2 * d * cfg.n_kv_heads * cfg.hd \
        + cfg.n_heads * cfg.hd * d
    ffn = cfg.n_experts_active * 3 * d * f + d * cfg.n_experts
    ffn += 3 * d * f * cfg.n_shared_experts
    per_layer = attn + ffn + 2 * d
    emb = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)
    return cfg.n_layers * per_layer + emb + d
