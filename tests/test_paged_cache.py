"""Block-paged KV cache + HDP-aware paged decode.

Load-bearing guarantees pinned here:

* page alloc/free/reuse stays consistent under continuous-batching churn
  (no page ever owned by two slots, free list conserved);
* paged decode is token-for-token identical to the dense `SlotCache`
  decode — with HDP off, and with HDP on under the static fixed-point
  grid (calib="none", the write-time-scout regime the paged backend
  always operates in);
* pruned pages are NEVER gathered: poisoning their full-precision K/V
  with NaN cannot change the output (the FUM contract);
* batched bucketed prefill groups same-bucket requests into fewer jit
  calls, and chunked prefill of a long prompt matches one-shot prefill.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import AttnSpec
from repro.configs import get_config
from repro.configs.base import reduced
from repro.core.config import HDPConfig
from repro.core.hdp import decode_scout
from repro.models.attention import (_fixed_split, _mask_bias,
                                    hdp_paged_decode_attention, scout_int8)
from repro.serving import Engine, Request
from repro.serving.kv_cache import PagedKVCache

F32 = jnp.float32


def _prompts(n, lo=4, hi=24, seed=0, vocab=250):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def _qwen(calib=None, enabled=True):
    cfg = reduced(get_config("qwen2-1.5b"))
    hdp = cfg.hdp.replace(enabled=enabled)
    if calib is not None:
        hdp = hdp.replace(calib=calib)
    return cfg.replace(hdp=hdp)


def _serve(cfg, params, prompts, max_new=5, **kw):
    eng = Engine(cfg, params=params, max_batch=2, max_len=64,
                 prefill_buckets=(16, 32), **kw)
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid, p, max_new_tokens=max_new))
    res = eng.run()
    return eng, {u: r.tokens for u, r in res.items()}


# ---------------------------------------------------------------- pool unit
def test_page_alloc_free_reuse():
    cfg = _qwen()
    pool = PagedKVCache(cfg, batch=3, max_len=32)  # block_k=2 -> 16 pages/slot
    total_free = len(pool._free)
    a = pool.alloc(0, 10)           # 5 pages
    b = pool.alloc(1, 3)            # 2 pages
    assert len(a) == 5 and len(b) == 2
    assert not set(a) & set(b), "pages shared between slots"
    assert 0 not in a + b, "scratch page must never be allocated"
    assert (pool._table[0, :5] == a).all() and (pool._table[0, 5:] == 0).all()
    pool.free(0)
    assert (pool._table[0] == 0).all()
    c = pool.alloc(2, 12)           # 6 pages; reuses slot 0's freed pages
    assert set(c) & set(a), "freed pages must be reused"
    pool.free(1)
    pool.free(2)
    assert len(pool._free) == total_free, "free list not conserved"
    assert pool.pages_in_use == 0
    with pytest.raises(ValueError):
        pool.alloc(0, 33)           # beyond max_len


def test_pool_exhaustion_is_impossible_within_capacity():
    cfg = _qwen()
    pool = PagedKVCache(cfg, batch=2, max_len=16)
    pool.alloc(0, 16)
    pool.alloc(1, 16)               # full occupancy still fits
    assert pool.pages_in_use == 2 * pool.pages_per_slot


def test_engine_churn_recycles_pages():
    # prefix_cache=False pins the pure-recycling invariant: with the
    # cache on, finished prompts intentionally stay resident (see
    # tests/test_prefix_cache.py for the shared-substrate invariants)
    cfg = _qwen(calib="none")
    eng, toks = _serve(cfg, None, _prompts(6, seed=1), max_new=3,
                       prefix_cache=False)
    assert len(toks) == 6 and all(len(t) == 3 for t in toks.values())
    # 6 requests through 2 slots: peak occupancy must stay bounded by the
    # two-slot working set, i.e. pages were freed and reused
    assert eng.pages.peak_pages <= 2 * eng.pages.pages_per_slot
    assert eng.pages.pages_in_use == 0  # all freed at drain


# ------------------------------------------------------- paged == dense
@pytest.mark.parametrize("mode", ["hdp_off", "hdp_calib_none", "hdp_stock"])
def test_paged_decode_equals_dense_decode(mode):
    """Token-for-token identity on the seed qwen2 reduced config.

    "hdp_stock" serves the config exactly as registered (calib="max"):
    the paged engine pins calib="none" internally, so it must match a
    dense engine given the same effective (calib-free) config."""
    cfg = _qwen(enabled=False) if mode == "hdp_off" else \
        _qwen() if mode == "hdp_stock" else _qwen(calib="none")
    prompts = _prompts(4, seed=3)
    # cross-layout identity needs the fp32 pool: the default int8 store
    # round-trips K/V at prefill time, which the dense cache never does
    eng, paged = _serve(cfg, None, prompts, attn=AttnSpec(kv_dtype="fp32"))
    if mode == "hdp_stock":
        assert eng.cfg.hdp.calib == "none", "paged engine must pin calib"
        cfg = _qwen(calib="none")
    _, dense = _serve(cfg, eng.params, prompts, attn=AttnSpec(layout="dense"))
    assert paged == dense, f"{mode}: paged {paged} != dense {dense}"


def test_paged_engine_emits_page_stats():
    cfg = _qwen()   # stock calibration: stats path, no token-equality claim
    eng, toks = _serve(cfg, None, _prompts(3, seed=5), collect_stats=True)
    s = eng.summary()
    assert s["stat_samples"] > 0
    assert 0.0 <= s["page_sparsity"] <= 1.0
    assert s["cache_backend"] == "paged"
    assert s["cache_bytes"] <= s["cache_bytes_pool"]


def test_paged_kernel_reports_block_fill():
    """With stats on, the Pallas paged kernel's route counts the kept
    pages it walks and the pages of its compute blocks; ``summary()``
    gives their ratio as ``paged_block_fill``. The XLA stage walks no
    blocks and reports none."""
    cfg = _qwen()
    eng, toks = _serve(cfg, None, _prompts(3, seed=5), collect_stats=True,
                       attn=AttnSpec(backend="pallas"))
    s = eng.summary()
    assert 0 < s["kernel_pages"] <= s["kernel_block_pages"]
    assert s["paged_block_fill"] == s["kernel_pages"] / s["kernel_block_pages"]
    eng, xla = _serve(cfg, eng.params, _prompts(3, seed=5), collect_stats=True)
    assert "paged_block_fill" not in eng.summary()
    assert xla == toks


# ------------------------------------------------------------ FUM contract
def test_pruned_pages_never_gathered():
    """Poisoning pruned pages' full-precision K/V cannot change the output."""
    rng = jax.random.PRNGKey(0)
    B, N, G, hd, ps, nP = 2, 2, 2, 8, 4, 8
    P = 1 + B * nP
    hdp = HDPConfig(block_q=1, block_k=ps, rho_b=0.5, causal=True,
                    head_pruning=False, calib="none")
    ks = jax.random.normal(jax.random.fold_in(rng, 1), (P, N, ps, hd), F32)
    vs = jax.random.normal(jax.random.fold_in(rng, 2), (P, N, ps, hd), F32)
    ik = scout_int8(ks, hdp)
    q = jax.random.normal(jax.random.fold_in(rng, 3), (B, N, G, 1, hd), F32)
    table = jnp.arange(1, P, dtype=jnp.int32).reshape(B, nP)
    pos = jnp.full((B, 1), nP * ps - 1, jnp.int32)   # every page visible
    q_pos = pos[:, None, None, :]
    ar = jnp.arange(nP * ps)
    k_pos = jnp.where(ar[None] <= pos, ar, -1)[:, None, None, :]

    out, _ = hdp_paged_decode_attention(
        q, ks, vs, ik, table, q_pos=q_pos, k_pos=k_pos, hdp=hdp)

    # reconstruct the keep mask exactly as the kernel does
    ik_full = jnp.swapaxes(ik[table], 2, 3).reshape(
        B, nP * ps, N, hd).astype(F32)
    _, iq, _ = _fixed_split(q, hdp)
    s_int = jnp.einsum("bngqh,bsnh->bngqs", iq, ik_full,
                       preferred_element_type=F32)
    valid = _mask_bias(q_pos, k_pos, hdp.causal, 0)
    keep, _, _, _, head_kept = decode_scout(s_int, valid, hdp)
    fetched = (keep & head_kept[..., None]).any(axis=(1, 2))     # [B, nP]
    pruned_pages = np.asarray(jnp.where(fetched, 0, table)).ravel()
    pruned_pages = pruned_pages[pruned_pages > 0]
    assert pruned_pages.size > 0, "test needs some pruned pages; lower rho_b"

    poison = jnp.asarray(pruned_pages)
    ks_bad = ks.at[poison].set(jnp.nan)
    vs_bad = vs.at[poison].set(jnp.nan)
    out_bad, _ = hdp_paged_decode_attention(
        q, ks_bad, vs_bad, ik, table, q_pos=q_pos, k_pos=k_pos, hdp=hdp)
    assert bool(jnp.isfinite(out_bad).all()), \
        "NaN leaked: a pruned page was gathered"
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_bad))


# ------------------------------------------------- batched/chunked prefill
@pytest.mark.slow  # spins one batched + four solo engines
def test_batched_prefill_groups_buckets():
    cfg = _qwen(calib="none")
    # 4 same-bucket prompts over 4 slots -> a single stacked prefill call
    prompts = [_prompts(1, lo=10, hi=14, seed=s)[0] for s in range(4)]
    eng = Engine(cfg, max_batch=4, max_len=64, prefill_buckets=(16, 32))
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid, p, max_new_tokens=3))
    res = eng.run()
    assert eng.metrics["prefill_calls"] == 1
    # each request must still decode exactly like a solo engine
    for uid, p in enumerate(prompts):
        solo = Engine(cfg, params=eng.params, max_batch=1, max_len=64,
                      prefill_buckets=(16, 32))
        solo.submit(Request(99, p, max_new_tokens=3))
        assert res[uid].tokens == solo.run()[99].tokens


def test_chunked_prefill_matches_one_shot():
    # exact at tau_h=0 (all registered configs): with tau_h > 0, HDP's
    # early head gate applies per forward call, so chunked gating may
    # differ from whole-prompt gating (documented in Engine._prefill_long)
    cfg = _qwen(calib="none")
    assert cfg.hdp.tau_h == 0.0
    prompt = _prompts(1, lo=40, hi=41, seed=9)[0]     # 40 > largest bucket
    eng = Engine(cfg, max_batch=2, max_len=64, prefill_buckets=(8, 16))
    eng.submit(Request(0, prompt, max_new_tokens=5))
    chunked = eng.run()[0].tokens
    one = Engine(cfg, params=eng.params, max_batch=2, max_len=64,
                 prefill_buckets=(64,))
    one.submit(Request(0, prompt, max_new_tokens=5))
    assert chunked == one.run()[0].tokens


def test_chunked_prefill_sliding_window():
    """Chunk q against a longer cache must not trip local_attention's
    aligned-q/k path (h2o-danube: sliding_window=16, HDP off)."""
    cfg = reduced(get_config("h2o-danube-1.8b"))
    cfg = cfg.replace(hdp=cfg.hdp.replace(enabled=False))
    prompt = _prompts(1, lo=40, hi=41, seed=13)[0]
    eng = Engine(cfg, max_batch=2, max_len=128, prefill_buckets=(32,))
    eng.submit(Request(0, prompt, max_new_tokens=4))
    chunked = eng.run()[0].tokens
    one = Engine(cfg, params=eng.params, max_batch=2, max_len=128,
                 prefill_buckets=(64,))
    one.submit(Request(0, prompt, max_new_tokens=4))
    assert chunked == one.run()[0].tokens


# ------------------------------------------------------------ kernel route
@pytest.mark.slow  # interpret-mode kernel per layer per step
@pytest.mark.parametrize("arch", [
    "qwen2-1.5b",
    "h2o-danube-1.8b",  # sliding window: pallas must fall back to xla
])
def test_pallas_attn_backend_matches_xla(arch):
    cfg = reduced(get_config(arch))
    cfg = cfg.replace(hdp=cfg.hdp.replace(calib="none"))
    prompts = _prompts(2, seed=11)
    eng, xla = _serve(cfg, None, prompts, max_new=4)
    _, pallas = _serve(cfg, eng.params, prompts, max_new=4,
                       attn=AttnSpec(backend="pallas"))
    assert xla == pallas
