"""Self-speculative decode: draft + batched verify + frontier rollback.

Load-bearing guarantees pinned here:

* speculative decode is token-for-token identical to horizon-1 greedy
  decode across paged and dense layouts, at every draft length —
  including mid-round EOS, staggered budgets, and degenerate drafts
  whose proposals are never accepted (the verify's exact tokens carry
  every round);
* the multi-query verify's per-query-row scout reproduces the sequential
  single-step masks exactly (unit conformance, kernel path included);
* the draft pass never reads the full-precision K pool: its scores come
  from the two int8 scout copies (NaN-poisoning all of k_pages leaves
  the draft's output unchanged);
* rejected speculative writes are rolled back by NaN-poisoning their K —
  the frontier invariant (rewrite-before-read) is self-enforcing, and
  generation still completes byte-identically through the poison;
* rollback composes with prefix-cache sharing: a COW'd tail page absorbs
  the speculative staging while the shared original's bytes never move,
  and sub-floor pages stay fenced;
* the speculative round donates the serving cache and take()/put() guard
  stale handles, exactly like the fused horizon loop;
* spec_rounds / draft_tokens / accepted_tokens count only slots that
  really decoded (parked slots are masked), and the env/kwarg plumbing
  (REPRO_SPEC_DECODE / REPRO_DRAFT_LEN) mirrors the horizon knobs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import AttnSpec, DraftProfile
from repro.configs import get_config
from repro.configs.base import reduced
from repro.core.config import HDPConfig
from repro.models.attention import hdp_paged_decode_attention, scout_int8
from repro.serving import Engine, Request
from repro.serving.kv_cache import DonatedCacheError

F32 = jnp.float32

#: a draft whose head gate kills every head: proposals degenerate to a
#: constant token, so almost every round rejects almost everything —
#: the zero-acceptance stress shape
DEAD_DRAFT = DraftProfile(tau_h=1e9)


def _prompts(n, lo=4, hi=24, seed=0, vocab=250):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def _qwen(calib="none", enabled=True):
    cfg = reduced(get_config("qwen2-1.5b"))
    return cfg.replace(hdp=cfg.hdp.replace(enabled=enabled, calib=calib))


def _serve(cfg, params, prompts, *, max_new=5, stagger=True, **kw):
    eng = Engine(cfg, params=params, max_batch=2, max_len=64,
                 prefill_buckets=(16, 32), **kw)
    for uid, p in enumerate(prompts):
        mn = max_new + (uid % 3 if stagger else 0)
        eng.submit(Request(uid, p, max_new_tokens=mn))
    res = eng.run()
    return eng, {u: r.tokens for u, r in res.items()}


# ------------------------------------------------------------ token identity
@pytest.mark.parametrize("layout", [
    "paged",
    pytest.param("dense", marks=pytest.mark.slow),
])
def test_spec_matches_single_step(layout):
    """Staggered budgets force slots to finish mid-round while their batch
    neighbors keep speculating — output must not notice, at any k."""
    cfg = _qwen()
    kw = {"attn": AttnSpec(layout=layout)}
    prompts = _prompts(4, seed=3)
    eng, base = _serve(cfg, None, prompts, spec_decode=False,
                       decode_horizon=1, **kw)
    for k in (1, 3, 4, 8):
        _, got = _serve(cfg, eng.params, prompts, spec_decode=True,
                        draft_len=k, **kw)
        assert got == base, f"{layout} draft_len={k}: {got} != {base}"


def test_spec_matches_single_step_no_hdp():
    """With HDP off there is no scout to draft with: the draft degrades
    to an exact proposer and the round must still be identity-preserving.
    An exact self-draft under greedy decode must also be fully accepted —
    a lower rate would mean the degraded draft reads state the staging
    path skipped (the K-write skip is HDP-gated for exactly this)."""
    cfg = _qwen(enabled=False)
    prompts = _prompts(3, seed=5)
    eng, base = _serve(cfg, None, prompts, spec_decode=False,
                       decode_horizon=1, stagger=False)
    # uniform budgets: with staggered budgets a slot drafts past its own
    # remaining budget (the round width tracks the LONGEST) and those
    # never-committable proposals honestly count against acceptance
    e2, got = _serve(cfg, eng.params, prompts, spec_decode=True, draft_len=4,
                     stagger=False)
    assert got == base
    assert e2.summary()["acceptance_rate"] == 1.0


def test_eos_mid_round_matches_single_step():
    cfg = _qwen()
    eng = Engine(cfg, max_batch=1, max_len=64, spec_decode=False,
                 decode_horizon=1)
    eng.submit(Request(0, _prompts(1, seed=2)[0], max_new_tokens=8))
    ref = eng.run()[0].tokens
    j = next((i for i in range(1, len(ref)) if ref[i] not in ref[:i]), None)
    if j is None:
        pytest.skip("degenerate generation: all tokens identical")
    for k in (2, 4, 8):
        e2 = Engine(cfg, params=eng.params, max_batch=1, max_len=64,
                    spec_decode=True, draft_len=k)
        e2.submit(Request(0, _prompts(1, seed=2)[0], max_new_tokens=8,
                          eos_id=ref[j]))
        assert e2.run()[0].tokens == ref[:j + 1], f"draft_len={k}"


def test_zero_acceptance_rounds_still_identical():
    """A draft whose proposals are (nearly) never accepted costs speed,
    never correctness: every committed token is the verify's exact one."""
    cfg = _qwen()
    prompts = _prompts(4, seed=7)
    eng, base = _serve(cfg, None, prompts, spec_decode=False,
                       decode_horizon=1)
    e2, got = _serve(cfg, eng.params, prompts, spec_decode=True,
                     draft_len=4, draft_profile=DEAD_DRAFT)
    assert got == base
    s = e2.summary()
    # the dead draft's constant proposals may occasionally collide with
    # the exact token — but most must be rejected
    assert s["acceptance_rate"] < 0.5
    assert s["spec_rounds"] > 0


# -------------------------------------------------------------- env plumbing
def test_spec_env_defaults(monkeypatch):
    monkeypatch.setenv("REPRO_SPEC_DECODE", "1")
    monkeypatch.setenv("REPRO_DRAFT_LEN", "3")
    eng = Engine(_qwen(), max_batch=1, max_len=32)
    assert eng.spec and eng.draft_len == 3
    # explicit kwargs win over the env
    eng = Engine(_qwen(), max_batch=1, max_len=32, spec_decode=False,
                 draft_len=5)
    assert not eng.spec and eng.draft_len == 5
    with pytest.raises(ValueError):
        Engine(_qwen(), max_batch=1, max_len=32, spec_decode=True,
               draft_len=0)


def test_spec_env_degrades_for_recurrent_families(monkeypatch):
    cfg = reduced(get_config("rwkv6-3b"))
    monkeypatch.setenv("REPRO_SPEC_DECODE", "1")
    assert not Engine(cfg, max_batch=1, max_len=32).spec  # env degrades
    with pytest.raises(ValueError, match="spec_decode"):
        Engine(cfg, max_batch=1, max_len=32, spec_decode=True)  # explicit raises


def test_spec_pins_static_calibration():
    """Speculative staging leaves garbage past the frontier; a
    data-dependent calibration scale would see it — spec engines pin the
    static grid on every layout, like the paged write-time scout does."""
    eng = Engine(_qwen(calib="max"), max_batch=1, max_len=32,
                 attn=AttnSpec(layout="dense"), spec_decode=True)
    assert eng.cfg.hdp.calib == "none"


# ------------------------------------------------------------------ counters
def test_spec_counters_masked_for_parked_slots():
    """One request on a 2-slot engine: the parked slot must not inflate
    draft/accept accounting, and the identities between the counters and
    the emitted tokens must hold exactly."""
    cfg = _qwen()
    eng = Engine(cfg, max_batch=2, max_len=64, prefill_buckets=(16, 32),
                 spec_decode=True, draft_len=4)
    eng.submit(Request(0, _prompts(1, seed=9)[0], max_new_tokens=7))
    res = eng.run()
    s = eng.summary()
    assert len(res[0].tokens) == 7
    assert s["spec_decode"] and s["draft_len"] == 4
    # one active slot: at most (draft_len-1) drafts per round (the round
    # width clamps to the remaining budget), parked slot unseen
    assert 0 < s["draft_tokens"] <= 3 * s["spec_rounds"]
    # every round commits >= 1 exact token; the rest are accepted drafts
    assert s["tokens_out"] == s["accepted_tokens"] + s["spec_rounds"]
    assert 0.0 <= s["acceptance_rate"] <= 1.0
    assert s["attn_backend_draft"]
    assert s["attn_backend_verify"]


# -------------------------------------------------- draft bandwidth contract
def _paged_inputs(seed, hdp, n_pages, B=2, N=2, G=2, hd=8, Sq=1):
    ps = hdp.block_k
    P = 1 + B * n_pages
    rng = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (B, N, G, Sq, hd), F32)
    ks = jax.random.normal(jax.random.fold_in(rng, 1), (P, N, ps, hd), F32)
    vs = jax.random.normal(jax.random.fold_in(rng, 2), (P, N, ps, hd), F32)
    ik = scout_int8(ks, hdp)
    table = jnp.arange(1, P, dtype=jnp.int32).reshape(B, n_pages)
    base = n_pages * ps - Sq
    pos = base + jnp.arange(Sq, dtype=jnp.int32)[None] \
        * jnp.ones((B, 1), jnp.int32)
    q_pos = pos[:, None, None, :]
    ar = jnp.arange(n_pages * ps)
    k_pos = jnp.where(ar[None] <= pos[:, -1:], ar, -1)[:, None, None, :]
    return q, ks, vs, ik, table, q_pos, k_pos


def test_draft_never_reads_fp_k_pool():
    """The scout-scores draft reads only the int8 copies + surviving V:
    NaN-poisoning the ENTIRE full-precision K pool changes nothing."""
    from repro.models.attention import scout_frac_int8
    hdp = HDPConfig(block_q=1, block_k=4, rho_b=0.5, causal=True,
                    head_pruning=False, calib="none")
    q, ks, vs, ik, table, q_pos, k_pos = _paged_inputs(0, hdp, n_pages=6)
    fk = scout_frac_int8(ks, hdp)
    for profile in (DraftProfile(), DraftProfile(scores="int")):
        clean, _ = hdp_paged_decode_attention(
            q, ks, vs, ik, table, q_pos=q_pos, k_pos=k_pos, hdp=hdp,
            draft=profile, fk_pool=fk)
        poisoned, _ = hdp_paged_decode_attention(
            q, jnp.full_like(ks, jnp.nan), vs, ik, table, q_pos=q_pos,
            k_pos=k_pos, hdp=hdp, draft=profile, fk_pool=fk)
        assert bool(jnp.isfinite(poisoned).all()), \
            f"{profile.scores}: draft read the full-precision K pool"
        np.testing.assert_array_equal(np.asarray(clean),
                                      np.asarray(poisoned))


def test_scout_draft_requires_frac_pool():
    """The scout score mode promises never to read the fp K pool; without
    the f_scout pool its IQ·FK^ term is underivable — misuse must raise,
    not silently serve lower-fidelity drafts."""
    hdp = HDPConfig(block_q=1, block_k=4, rho_b=0.5, causal=True,
                    head_pruning=False, calib="none")
    q, ks, vs, ik, table, q_pos, k_pos = _paged_inputs(2, hdp, n_pages=4)
    with pytest.raises(ValueError, match="f_scout"):
        hdp_paged_decode_attention(q, ks, vs, ik, table, q_pos=q_pos,
                                   k_pos=k_pos, hdp=hdp,
                                   draft=DraftProfile())


# --------------------------------------------------- per-query verify scout
@pytest.mark.parametrize("stage3", ["xla", "pallas_paged"])
def test_verify_rows_match_sequential_steps(stage3):
    """Row j of a multi-query verify call must equal the single-step
    decode at position j — keep masks, head gates and softmax alike
    (exact-match acceptance hangs off this equivalence)."""
    hdp = HDPConfig(block_q=1, block_k=4, rho_b=0.5, causal=True,
                    head_pruning=False, calib="none")
    Sq = 3
    q, ks, vs, ik, table, q_pos, k_pos = _paged_inputs(
        4, hdp, n_pages=4, Sq=Sq)
    multi, _ = hdp_paged_decode_attention(
        q, ks, vs, ik, table, q_pos=q_pos, k_pos=k_pos, hdp=hdp,
        per_query=True, stage3=stage3)
    for j in range(Sq):
        qj = q[:, :, :, j:j + 1]
        pj = q_pos[..., j:j + 1]
        ar = jnp.arange(k_pos.shape[-1])
        kj = jnp.where(ar[None, None, None, :] <= pj, ar, -1)
        single, _ = hdp_paged_decode_attention(
            qj, ks, vs, ik, table, q_pos=pj, k_pos=kj, hdp=hdp,
            stage3=stage3)
        np.testing.assert_allclose(
            np.asarray(multi[:, :, :, j]), np.asarray(single[:, :, :, 0]),
            atol=2e-5, rtol=2e-5,
            err_msg=f"{stage3}: verify row {j} != sequential step")


def test_verify_call_resolves_through_registry():
    """The verify AttnCall resolves to backends that declared multi-query
    capability; the draft call never lands on a Pallas kernel, and the
    block kernel serves no paged call at all."""
    from repro.attention import get_backend, resolve_backend
    from repro.models.attention import build_attn_call
    cfg = _qwen()
    ver = build_attn_call(cfg, mode="decode", paged=True, per_slot=True,
                          verify=True)
    assert get_backend("paged_hdp_decode").supports(ver)
    assert get_backend("pallas_paged_decode").supports(ver)
    assert not get_backend("pallas_hdp_block").supports(ver)
    assert resolve_backend(ver, AttnSpec(backend="xla")).name \
        == "paged_hdp_decode"
    drf = build_attn_call(cfg, mode="decode", paged=True, per_slot=True,
                          draft=DraftProfile())
    assert not get_backend("pallas_paged_decode").supports(drf)
    assert resolve_backend(drf, AttnSpec(backend="pallas")).name \
        == "paged_hdp_decode"          # kernels fall back for draft calls


# --------------------------------------------------------- rollback + poison
def test_rejected_speculative_writes_are_poisoned():
    """After a round with rejections, the K of every rejected staged
    position is poisoned (the rollback fence: NaN for the fp32 pool, the
    -128 sentinel code for the quantized one) — and generation still
    drains byte-identically through it (rewrite-before-read holds)."""
    cfg = _qwen()
    prompt = _prompts(1, seed=13)[0]
    base = Engine(cfg, max_batch=1, max_len=64, spec_decode=False,
                  decode_horizon=1)
    base.submit(Request(0, prompt, max_new_tokens=8))
    ref = base.run()[0].tokens

    k = 4
    eng = Engine(cfg, params=base.params, max_batch=1, max_len=64,
                 spec_decode=True, draft_len=k, draft_profile=DEAD_DRAFT)
    eng.submit(Request(0, prompt, max_new_tokens=8))
    start = len(prompt) - 1
    eng.step()                               # admit + first round
    committed = len(eng._active[0]["generated"]) if 0 in eng._active else 8
    assert committed < k, "dead draft unexpectedly fully accepted"
    ps = eng.pages.page_size
    pages = eng.pages.slot_pages(0)
    poisoned = np.asarray(eng.pages.poison_view())   # dtype-independent
    for p in range(start + committed, start + k):
        page, off = pages[p // ps], p % ps
        assert poisoned[:, page, :, off].all(), \
            f"rejected staged position {p} not poisoned"
    # committed frontier (last committed token's write) stays clean
    last = start + committed - 1
    assert not poisoned[:, pages[last // ps], :, last % ps].any()
    assert eng.run()[0].tokens == ref


def test_spec_rollback_respects_cow_and_write_floor():
    """Full-prompt prefix hit: the resume + speculative staging land in
    the COW'd tail page; the shared original's bytes never change even
    while rounds stage and roll back across it."""
    cfg = _qwen()
    rng = np.random.default_rng(11)
    donor = rng.integers(1, 250, size=13).tolist()
    eng = Engine(cfg, max_batch=1, max_len=64, prefill_buckets=(16, 32),
                 prefix_cache=True, spec_decode=True, draft_len=4)
    eng.submit(Request(0, donor, max_new_tokens=3))
    eng.run()
    matched = eng.prefix.match(donor[:12])
    tail_page = matched[-1]
    eng.pages.allocator.unref(matched)
    before = np.asarray(eng.pages.cache["k_pages"][:, tail_page])

    eng.submit(Request(1, donor[:12], max_new_tokens=3))   # full hit -> COW
    res = eng.run()
    assert eng.summary()["cow_copies"] == 1
    after = np.asarray(eng.pages.cache["k_pages"][:, tail_page])
    np.testing.assert_array_equal(before, after)

    solo = Engine(cfg, params=eng.params, max_batch=1, max_len=64,
                  prefill_buckets=(16, 32), prefix_cache=False,
                  spec_decode=False, decode_horizon=1)
    solo.submit(Request(9, donor[:12], max_new_tokens=3))
    assert res[1].tokens == solo.run()[9].tokens


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_spec_prefix_cache_identity(prefix_cache):
    """Shared-prefix workload: speculative decode with the prefix cache
    on/off is byte-identical to the non-speculative engine."""
    cfg = _qwen()
    rng = np.random.default_rng(17)
    shared = rng.integers(1, 250, size=16).tolist()
    prompts = [shared + rng.integers(1, 250, size=4 + i).tolist()
               for i in range(3)] + [shared[:12]]
    outs = []
    params = None
    for spec in (False, True):
        eng = Engine(cfg, params=params, max_batch=2, max_len=96,
                     prefill_buckets=(16, 32), prefix_cache=prefix_cache,
                     spec_decode=spec, draft_len=4, decode_horizon=1)
        params = eng.params
        for uid, p in enumerate(prompts):
            eng.submit(Request(uid, p, max_new_tokens=4))
        outs.append({u: r.tokens for u, r in eng.run().items()})
    assert outs[0] == outs[1]


# ----------------------------------------------------------------- donation
def test_spec_round_donates_cache():
    """The speculative round jit aliases the page pool in place — after
    one round the pre-round pool buffer is deleted, and stale handles
    raise through take()/put()."""
    cfg = _qwen()
    eng = Engine(cfg, max_batch=2, max_len=64, spec_decode=True, draft_len=4)
    for uid, p in enumerate(_prompts(2, seed=5)):
        eng.submit(Request(uid, p, max_new_tokens=4))
    eng._admit()
    old = eng.pages.cache
    eng.step()
    assert all(old[k].is_deleted() for k in old), \
        "donation rejected: speculative round allocated a second page pool"
    cache = eng.pages.take()
    with pytest.raises(DonatedCacheError):
        _ = eng.pages.cache
    eng.pages.put(cache)
    eng.run()

    dense = Engine(cfg, params=eng.params, max_batch=2, max_len=64,
                   attn=AttnSpec(layout="dense"), spec_decode=True,
                   draft_len=4)
    dense.submit(Request(0, _prompts(1, seed=5)[0], max_new_tokens=4))
    dense._admit()
    old_k = dense.slots.cache["k"]
    dense.step()
    assert old_k.is_deleted()
    dense.run()
