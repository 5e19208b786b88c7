"""Zero-copy decode hot path: donation, fused multi-step decode, FUM scan.

Load-bearing guarantees pinned here:

* horizon-H fused decode is token-for-token identical to H=1 across
  paged and dense layouts — including EOS firing mid-horizon and slots
  finishing while others continue;
* the decode step donates the serving cache: after one step the old page
  pool buffer is deleted (aliased in place, not copied), and a stale
  handle taken around a donating call cannot be reused
  (``DonatedCacheError``);
* the FUM contract survives donation and the chunked page scan: memory
  the page table never references (free pages) can be NaN-poisoned
  without changing a single generated token, and the >page_chunk scan
  path agrees with the one-shot gather while never touching pruned
  pages;
* ``Engine.run(max_steps)`` exhaustion warns (or raises on strict=True),
  marks the affected Results incomplete, and a follow-up run() finishes
  them.
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
from repro.models.attention import hdp_paged_decode_attention, scout_int8
from repro.serving import Engine, Request
from repro.serving.kv_cache import DonatedCacheError

F32 = jnp.float32


def _prompts(n, lo=4, hi=24, seed=0, vocab=250):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def _qwen(calib="none", enabled=True):
    cfg = reduced(get_config("qwen2-1.5b"))
    return cfg.replace(hdp=cfg.hdp.replace(enabled=enabled, calib=calib))


def _serve(cfg, params, prompts, horizon, *, max_new=5, stagger=True, **kw):
    eng = Engine(cfg, params=params, max_batch=2, max_len=64,
                 prefill_buckets=(16, 32), decode_horizon=horizon, **kw)
    for uid, p in enumerate(prompts):
        mn = max_new + (uid % 3 if stagger else 0)
        eng.submit(Request(uid, p, max_new_tokens=mn))
    res = eng.run()
    return eng, {u: r.tokens for u, r in res.items()}


# ------------------------------------------------------ fused loop identity
@pytest.mark.parametrize("layout", [
    "paged",
    pytest.param("dense", marks=pytest.mark.slow),
])
def test_horizon_matches_single_step(layout):
    """Staggered budgets force slots to finish mid-horizon while their
    batch neighbors keep decoding — output must not notice."""
    cfg = _qwen()
    kw = {"attn": AttnSpec(layout=layout)}
    prompts = _prompts(4, seed=3)
    eng, h1 = _serve(cfg, None, prompts, 1, **kw)
    for horizon in (3, 4, 8):
        _, hH = _serve(cfg, eng.params, prompts, horizon, **kw)
        assert hH == h1, f"{layout} horizon={horizon}: {hH} != {h1}"


def test_eos_mid_horizon_matches_single_step():
    cfg = _qwen()
    eng = Engine(cfg, max_batch=1, max_len=64, decode_horizon=1)
    eng.submit(Request(0, _prompts(1, seed=2)[0], max_new_tokens=8))
    ref = eng.run()[0].tokens
    j = next((i for i in range(1, len(ref)) if ref[i] not in ref[:i]), None)
    if j is None:
        pytest.skip("degenerate generation: all tokens identical")
    outs = {}
    for horizon in (1, 4, 8):
        e2 = Engine(cfg, params=eng.params, max_batch=1, max_len=64,
                    decode_horizon=horizon)
        e2.submit(Request(0, _prompts(1, seed=2)[0], max_new_tokens=8,
                          eos_id=ref[j]))
        outs[horizon] = e2.run()[0].tokens
    assert all(o == ref[:j + 1] for o in outs.values()), outs


def test_decode_horizon_env_default(monkeypatch):
    monkeypatch.setenv("REPRO_DECODE_HORIZON", "3")
    assert Engine(_qwen(), max_batch=1, max_len=32).horizon == 3
    # explicit kwarg wins over the env
    assert Engine(_qwen(), max_batch=1, max_len=32,
                  decode_horizon=1).horizon == 1
    with pytest.raises(ValueError):
        Engine(_qwen(), max_batch=1, max_len=32, decode_horizon=0)


# ----------------------------------------------------------------- donation
def test_decode_step_donates_cache():
    """The decode jit aliases the page pool in place: after one step the
    pre-step pool buffer is deleted — no second copy of the pool exists."""
    cfg = _qwen()
    eng = Engine(cfg, max_batch=2, max_len=64, decode_horizon=4)
    for uid, p in enumerate(_prompts(2, seed=5)):
        eng.submit(Request(uid, p, max_new_tokens=4))
    eng._admit()
    old = eng.pages.cache
    eng.step()
    assert all(old[k].is_deleted() for k in old), \
        "donation rejected: decode step allocated a second page pool"
    eng.run()

    dense = Engine(cfg, params=eng.params, max_batch=2, max_len=64,
                   attn=AttnSpec(layout="dense"))
    dense.submit(Request(0, _prompts(1, seed=5)[0], max_new_tokens=4))
    dense._admit()
    old_k = dense.slots.cache["k"]
    dense.step()
    assert old_k.is_deleted()
    dense.run()


def test_decode_failure_restores_cache_handle():
    """A decode-trace failure must not strand the engine: the donated
    handle is restored so the real error surfaces and the engine stays
    usable, not a later DonatedCacheError."""
    from repro.attention import BackendUnsupported
    cfg = _qwen()
    eng = Engine(cfg, max_batch=1, max_len=32,
                 attn=AttnSpec(decode="pallas_flash", allow_fallback=False))
    eng.submit(Request(0, [1, 2, 3], max_new_tokens=2))
    with pytest.raises(BackendUnsupported):
        eng.step()
    _ = eng.pages.cache               # handle restored


def test_stale_cache_handle_guard():
    cfg = _qwen()
    eng = Engine(cfg, max_batch=1, max_len=32)
    cache = eng.pages.take()
    with pytest.raises(DonatedCacheError):
        _ = eng.pages.cache
    eng.pages.put(cache)
    with pytest.raises(DonatedCacheError):
        eng.pages.put(cache)          # put without a prior take
    _ = eng.pages.cache               # restored handle is live again


def test_poisoned_free_pages_never_read_with_donation():
    """NaN-poisoning pool memory the page tables never reference cannot
    change a single token: decode reads only table-mapped pages (pruned
    ones scratch-redirected), through the donated in-place pool."""
    cfg = _qwen()
    prompts = _prompts(2, seed=7)

    eng, clean = _serve(cfg, None, prompts, 4, stagger=False)

    eng2 = Engine(cfg, params=eng.params, max_batch=2, max_len=64,
                  prefill_buckets=(16, 32), decode_horizon=4)
    for uid, p in enumerate(prompts):
        eng2.submit(Request(uid, p, max_new_tokens=5))
    eng2.step()                        # admit + first horizon
    free = list(eng2.pages._free)
    assert free, "test needs unallocated pages"
    c = eng2.pages.cache
    idx = jnp.asarray(free)
    if c["k_pages"].dtype == jnp.int8:
        # quantized pool: poison through both sentinel channels — the
        # -128 code (position-granular) AND NaN page scales (K and V
        # alike; these pages are never gathered, so even the V-poison
        # the live paths must avoid is safe here)
        from repro.core.quant import POISON_CODE
        vp = c["v_pages"]
        v_bad = (vp.at[:, idx].set(POISON_CODE) if vp.dtype == jnp.int8
                 else vp.at[:, idx].set(jnp.nan))
        eng2.pages.cache = {
            **c,
            "k_pages": c["k_pages"].at[:, idx].set(POISON_CODE),
            "v_pages": v_bad,
            "k_scale": c["k_scale"].at[:, idx].set(jnp.nan),
            "v_scale": c["v_scale"].at[:, idx].set(jnp.nan),
        }
    else:
        eng2.pages.cache = {
            **c,
            "k_pages": c["k_pages"].at[:, idx].set(jnp.nan),
            "v_pages": c["v_pages"].at[:, idx].set(jnp.nan),
        }
    res = eng2.run()
    poisoned = {u: r.tokens for u, r in res.items()}
    assert poisoned == clean, "NaN leaked from never-referenced pool pages"


# ------------------------------------------------- gather-free XLA scan path
def _paged_inputs(seed, hdp, n_pages, B=2, N=2, G=2, hd=8):
    ps = hdp.block_k
    P = 1 + B * n_pages
    rng = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (B, N, G, 1, hd), F32)
    ks = jax.random.normal(jax.random.fold_in(rng, 1), (P, N, ps, hd), F32)
    vs = jax.random.normal(jax.random.fold_in(rng, 2), (P, N, ps, hd), F32)
    ik = scout_int8(ks, hdp)
    table = jnp.arange(1, P, dtype=jnp.int32).reshape(B, n_pages)
    pos = jnp.full((B, 1), n_pages * ps - 1, jnp.int32)
    q_pos = pos[:, None, None, :]
    ar = jnp.arange(n_pages * ps)
    k_pos = jnp.where(ar[None] <= pos, ar, -1)[:, None, None, :]
    return q, ks, vs, ik, table, q_pos, k_pos


def test_paged_scan_matches_one_shot_gather():
    """Forcing the chunked online-softmax path (page_chunk < Sk) agrees
    with the one-shot gather to float tolerance."""
    hdp = HDPConfig(block_q=1, block_k=4, rho_b=0.5, causal=True,
                    head_pruning=False, calib="none")
    q, ks, vs, ik, table, q_pos, k_pos = _paged_inputs(0, hdp, n_pages=8)
    one, _ = hdp_paged_decode_attention(
        q, ks, vs, ik, table, q_pos=q_pos, k_pos=k_pos, hdp=hdp)
    for chunk in (4, 8, 12):
        scan, _ = hdp_paged_decode_attention(
            q, ks, vs, ik, table, q_pos=q_pos, k_pos=k_pos, hdp=hdp,
            page_chunk=chunk)
        np.testing.assert_allclose(np.asarray(scan), np.asarray(one),
                                   atol=2e-5, rtol=2e-5)


def test_paged_scan_never_reads_pruned_pages():
    """The NaN-poison FUM contract holds on the chunked scan path too."""
    from repro.core.hdp import decode_scout
    from repro.models.attention import _fixed_split, _mask_bias
    hdp = HDPConfig(block_q=1, block_k=4, rho_b=0.5, causal=True,
                    head_pruning=False, calib="none")
    q, ks, vs, ik, table, q_pos, k_pos = _paged_inputs(1, hdp, n_pages=8)
    out, _ = hdp_paged_decode_attention(
        q, ks, vs, ik, table, q_pos=q_pos, k_pos=k_pos, hdp=hdp,
        page_chunk=8)

    B, nP = table.shape
    ik_full = jnp.swapaxes(ik[table], 2, 3).reshape(
        B, nP * hdp.block_k, 2, 8).astype(F32)
    _, iq, _ = _fixed_split(q, hdp)
    s_int = jnp.einsum("bngqh,bsnh->bngqs", iq, ik_full,
                       preferred_element_type=F32)
    valid = _mask_bias(q_pos, k_pos, hdp.causal, 0)
    keep, _, _, _, head_kept = decode_scout(s_int, valid, hdp)
    fetched = (keep & head_kept[..., None]).any(axis=(1, 2))
    pruned = np.asarray(jnp.where(fetched, 0, table)).ravel()
    pruned = pruned[pruned > 0]
    assert pruned.size > 0, "test needs some pruned pages; lower rho_b"

    poison = jnp.asarray(pruned)
    out_bad, _ = hdp_paged_decode_attention(
        q, ks.at[poison].set(jnp.nan), vs.at[poison].set(jnp.nan), ik,
        table, q_pos=q_pos, k_pos=k_pos, hdp=hdp, page_chunk=8)
    assert bool(jnp.isfinite(out_bad).all()), \
        "NaN leaked: the scan path read a pruned page"
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_bad))


# ----------------------------------------------------- paged scout kernel
def test_paged_scout_kernel_tokens_match_xla_scout(monkeypatch):
    """An int8 paged engine on the Pallas paged decode backend scouts in
    the paged scout kernel: its tokens and page sparsity equal those of
    the same engine with stage 1 sent back to the XLA scout, over a
    multi-step decode. With stats on, ``scout_pages`` counts the table
    columns the kernel walked, ceil((position + 1) / page) for every
    layer and decoded slot-step; the XLA scout counts none."""
    from repro.models import attention as A

    cfg = _qwen()
    prompts = _prompts(3, lo=3, hi=12, seed=11)
    pallas = AttnSpec(backend="pallas")
    eng, kern = _serve(cfg, None, prompts, 1, max_new=7, collect_stats=True,
                       attn=pallas)
    assert eng.resolved_backend("decode") == "pallas_paged_decode"
    assert eng.kv_dtype == "int8"
    s = eng.summary()
    ps, layers = eng.pages.page_size, cfg.n_layers
    walked = slots = 0
    for uid, p in enumerate(prompts):
        # each token comes from a decode step whose query sits at the
        # position before it: the first step replays the prompt's last
        for pos in range(len(p) - 1, len(p) - 1 + len(kern[uid])):
            walked += -(-(pos + 1) // ps)
            slots += 1
    assert s["scout_pages"] == layers * walked
    assert s["scout_samples"] == layers * slots

    monkeypatch.setattr(A, "_paged_scout_route", lambda *a, **k: False)
    eng2, xla = _serve(cfg, eng.params, prompts, 1, max_new=7,
                       collect_stats=True, attn=pallas)
    assert xla == kern
    s2 = eng2.summary()
    assert s2["scout_samples"] == 0 and s2["scout_pages"] == 0
    assert s2["page_sparsity"] == s["page_sparsity"]
    assert s2["head_sparsity"] == s["head_sparsity"]


@pytest.mark.parametrize("case,kernel", [
    ("decode", True), ("verify", False), ("window", False),
    ("fp32_pool", False), ("absmax", False)])
def test_paged_scout_route(case, kernel):
    """Stage 1 takes the paged scout kernel only for one causal query row
    per slot over an int8 static-grid pool under the paged kernel's
    stage 3; verify rows, a sliding window, fp32 pools and calibrated
    (absmax) scales keep the XLA scout. The kernel's presence shows in
    the stats it alone reports (``scout_pages``)."""
    from repro.core.quant import encode_pool, pool_scale
    hdp = HDPConfig(block_q=1, block_k=4, rho_b=0.5, causal=True,
                    head_pruning=False, calib="none")
    q, ks, vs, ik, table, q_pos, k_pos = _paged_inputs(2, hdp, n_pages=4)
    kw = dict(stage3="pallas_paged", return_stats=True)
    if case == "verify":
        q = jnp.concatenate([q, q], axis=3)
        q_pos = jnp.concatenate([q_pos - 1, q_pos], axis=3)
        kw["per_query"] = True
    elif case == "window":
        kw["window"] = 8
    if case != "fp32_pool":
        P, N = ks.shape[:2]
        ks, vs = encode_pool(ks, hdp.int_bits), encode_pool(vs, hdp.int_bits)
        scale = jnp.full((P, N), pool_scale(hdp.int_bits), F32)
        kw.update(k_scale=scale, v_scale=scale, kv_scale=(
            "absmax" if case == "absmax" else "grid"))
        ik = None
    out, stats = hdp_paged_decode_attention(
        q, ks, vs, ik, table, q_pos=q_pos, k_pos=k_pos, hdp=hdp, **kw)
    assert bool(jnp.isfinite(out).all())
    assert ("scout_pages" in stats) == kernel
    if kernel:
        np.testing.assert_array_equal(np.asarray(stats["scout_pages"]),
                                      table.shape[1])


# --------------------------------------------------------- run() exhaustion
def test_run_budget_exhaustion_warns_and_marks_incomplete():
    cfg = _qwen()
    # horizon-loop semantics under test: exactly one token per step (the
    # speculative round would commit several — pin it off for env legs)
    eng = Engine(cfg, max_batch=1, max_len=64, decode_horizon=1,
                 spec_decode=False)
    for uid, p in enumerate(_prompts(3, seed=11)):
        eng.submit(Request(uid, p, max_new_tokens=6))
    with pytest.warns(RuntimeWarning, match="step budget"):
        res = eng.run(max_steps=3)
    assert not res[0].complete and len(res[0].tokens) == 3
    assert not res[1].complete and res[1].tokens == []   # still queued
    # engine state was left intact: finishing the drain completes them
    res = eng.run()
    assert all(r.complete for r in res.values())
    assert all(len(r.tokens) == 6 for r in res.values())


def test_run_budget_exhaustion_strict_raises():
    cfg = _qwen()
    eng = Engine(cfg, max_batch=1, max_len=64)
    eng.submit(Request(0, _prompts(1, seed=12)[0], max_new_tokens=6))
    with pytest.raises(RuntimeError, match="step budget"):
        eng.run(max_steps=1, strict=True)
    assert not eng._results[0].complete
