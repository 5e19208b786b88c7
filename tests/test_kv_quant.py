"""Quantized KV pool invariants (the int8-first paged serving store).

Load-bearing guarantees pinned here:

* the shared per-page grid (core.quant) round-trips within half a grid
  step, saturates at the code range, never emits the reserved
  POISON_CODE, and is idempotent — dequantized values re-encode to the
  same codes and are fixed points of ``quantize_fixed`` (the property
  that lets every consumer downstream of a dequant share the fp32
  pipeline's maths verbatim);
* poison survives quantization through BOTH channels: the -128 sentinel
  decodes to NaN position-granularly, a NaN page scale poisons the
  whole page, and the finite scout views ignore either channel;
* the FUM no-DMA contract holds on int8 pools in every paged stage-3
  backend (XLA gather slab, XLA online-softmax page-chunk scan, and the
  gather-free Pallas kernel): poisoning pruned pages cannot change the
  output, a NaN-scaled *visible* page trips NaN;
* the quantized pipeline is bit-identical to the fp32 pipeline fed the
  same round-tripped values (power-of-two scale: the dequant multiply
  is exact in fp32);
* COW keeps the donor page's codes AND scale byte-identical, and
  prefix-cache hits under ``kv_dtype="int8"`` are token-identical to
  cold serves (the prefill-time round-trip guarantee);
* the serving summary reports the dtype-aware resident footprint
  (int8 <= 0.35x fp32 bytes per cached token).

Tests that pin int8-specific behavior set ``AttnSpec(kv_dtype=...)``
explicitly so the REPRO_KV_DTYPE CI legs cannot flip them.
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
from repro.core.quant import (POISON_CODE, decode_pool, encode_pool,
                              pool_scale, pool_view_finite, quantize_fixed)
from repro.models.attention import (_fixed_split, _mask_bias,
                                    hdp_paged_decode_attention, scout_int8)
from repro.serving import Engine, Request

F32 = jnp.float32
I8 = AttnSpec(kv_dtype="int8")


def _qwen(head_pruning=False):
    cfg = reduced(get_config("qwen2-1.5b"))
    return cfg.replace(hdp=cfg.hdp.replace(calib="none",
                                           head_pruning=head_pruning))


def _prompts(n, lo=4, hi=24, seed=0, vocab=250):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


# ------------------------------------------------------------- grid unit
def test_roundtrip_bound_and_idempotence():
    rng = np.random.default_rng(0)
    for ib in (2, 4, 6):
        s0 = pool_scale(ib)
        lim = 127 * s0
        x = np.concatenate([
            rng.uniform(-lim, lim, size=2000),          # in-range
            rng.uniform(lim * 1.01, lim * 64, size=50),  # saturating
            -rng.uniform(lim * 1.01, lim * 64, size=50),
        ]).astype(np.float32)
        codes = np.asarray(encode_pool(jnp.asarray(x), ib))
        assert codes.min() >= -127, "encode emitted the POISON_CODE"
        dq = np.asarray(decode_pool(jnp.asarray(codes), s0))
        inr = np.abs(x) < lim + s0 / 2
        assert np.abs(dq - x)[inr].max() <= s0 / 2 * (1 + 1e-6)
        assert (np.sign(x[~inr]) * dq[~inr] == lim).all(), "no saturation"
        # idempotence: decoded values re-encode to the same codes and sit
        # exactly on the fixed-point grid the attention maths snaps K to
        assert np.array_equal(
            np.asarray(encode_pool(jnp.asarray(dq), ib)), codes)
        np.testing.assert_array_equal(
            np.asarray(quantize_fixed(jnp.asarray(dq), ib)), dq)


def test_roundtrip_error_bound_property():
    pytest.importorskip(
        "hypothesis", reason="property sweep needs hypothesis "
        "(requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st
    from hypothesis.extra import numpy as hnp

    @settings(max_examples=30, deadline=None)
    @given(hnp.arrays(np.float32, (3, 4, 2, 8),
                      elements=st.floats(-1000, 1000, width=32)),
           st.integers(min_value=2, max_value=6))
    def check(x, ib):
        s0 = pool_scale(ib)
        lim = 127 * s0
        codes = np.asarray(encode_pool(jnp.asarray(x), ib))
        assert codes.min() >= -127
        dq = np.asarray(decode_pool(jnp.asarray(codes), s0))
        inr = np.abs(x) < lim + s0 / 2
        if inr.any():
            assert np.abs(dq - x)[inr].max() <= s0 / 2 * (1 + 1e-6)
        if (~inr).any():
            assert (np.sign(x[~inr]) * dq[~inr] == lim).all()
        assert np.array_equal(
            np.asarray(encode_pool(jnp.asarray(dq), ib)), codes)

    check()


def test_poison_survives_quantization():
    ib = 4
    s0 = pool_scale(ib)
    codes = jnp.asarray([[5, POISON_CODE, -127]], jnp.int8)
    dq = np.asarray(decode_pool(codes, s0))
    assert dq[0, 0] == 5 * s0 and dq[0, 2] == -127 * s0
    assert np.isnan(dq[0, 1]), "sentinel code must decode to NaN"
    # the scout view ignores BOTH poison channels: sentinel -> 0 and the
    # (per-page NaN scale) channel does not enter the static-grid view
    view = np.asarray(pool_view_finite(codes, ib))
    assert np.isfinite(view).all() and view[0, 1] == 0.0
    # page-granular: a NaN scale poisons every dequant of the page
    assert np.isnan(np.asarray(decode_pool(codes, jnp.nan))).all()


# ------------------------------------------- FUM contract on int8 pools
@pytest.mark.parametrize("stage3,page_chunk", [
    ("xla", 128),          # gather-slab path
    ("xla", 8),            # online-softmax page-chunk scan (Sk=32 > 8)
    ("pallas_paged", 128),  # gather-free kernel (interpret mode on CPU)
])
def test_quantized_pools_match_fp32_and_never_dma_pruned(stage3, page_chunk):
    """int8 pools: bit-parity with the fp32 pipeline on round-tripped
    values; poisoned pruned pages cannot change the output; a NaN-scaled
    visible page trips NaN (the stage-3 tripwire)."""
    rng = jax.random.PRNGKey(0)
    B, N, G, hd, ps, nP = 2, 2, 2, 8, 4, 8
    P = 1 + B * nP
    Sk = nP * ps
    hdp = HDPConfig(block_q=1, block_k=ps, rho_b=0.5, causal=True,
                    head_pruning=False, calib="none")
    ib = hdp.int_bits
    ks = jax.random.normal(jax.random.fold_in(rng, 1), (P, N, ps, hd), F32)
    vs = jax.random.normal(jax.random.fold_in(rng, 2), (P, N, ps, hd), F32)
    kc, vc = encode_pool(ks, ib), encode_pool(vs, ib)
    kscl = jnp.full((P, N), pool_scale(ib), F32)
    vscl = jnp.full((P, N), pool_scale(ib), F32)
    q = jax.random.normal(jax.random.fold_in(rng, 3), (B, N, G, 1, hd), F32)
    table = jnp.arange(1, P, dtype=jnp.int32).reshape(B, nP)
    pos = jnp.full((B, 1), Sk - 1, jnp.int32)      # every page visible
    q_pos = pos[:, None, None, :]
    ar = jnp.arange(Sk)
    k_pos = jnp.where(ar[None] <= pos, ar, -1)[:, None, None, :]
    kw = dict(q_pos=q_pos, k_pos=k_pos, hdp=hdp, stage3=stage3,
              page_chunk=page_chunk)

    out_q, _ = hdp_paged_decode_attention(
        q, kc, vc, None, table, k_scale=kscl, v_scale=vscl, **kw)
    assert bool(jnp.isfinite(out_q).all())

    # bit-parity: the fp32 pipeline fed the decoded values (and the
    # write-time scout copy of them) must agree exactly — the
    # power-of-two scale makes every dequant multiply exact
    k_rt, v_rt = pool_view_finite(kc, ib), pool_view_finite(vc, ib)
    out_fp, _ = hdp_paged_decode_attention(
        q, k_rt, v_rt, scout_int8(k_rt, hdp), table, **kw)
    np.testing.assert_array_equal(np.asarray(out_q), np.asarray(out_fp))

    # reconstruct the fetch decision exactly as stage 1 does
    ik = jnp.trunc(pool_view_finite(jnp.swapaxes(kc[table], 2, 3), ib))
    ik = ik.reshape(B, Sk, N, hd)
    _, iq, _ = _fixed_split(q, hdp)
    s_int = jnp.einsum("bngqh,bsnh->bngqs", iq, ik,
                       preferred_element_type=F32)
    valid = _mask_bias(q_pos, k_pos, hdp.causal, 0)
    keep, _, _, _, head_kept = decode_scout(s_int, valid, hdp)
    fetched = (keep & head_kept[..., None]).any(axis=(1, 2))     # [B, nP]
    pruned = np.asarray(jnp.where(fetched, 0, table)).ravel()
    pruned = pruned[pruned > 0]
    assert pruned.size > 0, "test needs pruned pages; lower rho_b"

    # poison pruned pages through every stage-3 channel: V codes, and
    # both per-page scales. (K codes stay intact — they ARE the stage-1
    # scout stream, which always reads every allocated page by design;
    # the no-DMA contract is that stage 3 never dequantizes a pruned
    # page, so NaN scales and V poison must be invisible.)
    bad = jnp.asarray(pruned)
    out_bad, _ = hdp_paged_decode_attention(
        q, kc, vc.at[bad].set(POISON_CODE), None,
        table, k_scale=kscl.at[bad].set(jnp.nan),
        v_scale=vscl.at[bad].set(jnp.nan), **kw)
    assert bool(jnp.isfinite(out_bad).all()), \
        "poison leaked: a pruned page was gathered"
    np.testing.assert_array_equal(np.asarray(out_q), np.asarray(out_bad))

    # ... and a NaN scale on a FETCHED page must trip NaN: the scale
    # channel does not perturb the static-grid scout, so the fetch
    # decision is unchanged and stage 3 must hit the poisoned dequant
    vis = np.asarray(jnp.where(fetched, table, 0))[0]
    vis = vis[vis > 0][0]
    out_nan, _ = hdp_paged_decode_attention(
        q, kc, vc, None, table, k_scale=kscl.at[vis].set(jnp.nan),
        v_scale=vscl, **kw)
    assert bool(jnp.isnan(out_nan[0]).any()), \
        "NaN-scale poison on a visible page did not surface"


# ------------------------------------------------------ engine invariants
def test_cow_keeps_donor_codes_and_scales():
    """A full-prefix hit extends the shared tail: COW must leave the
    donor's cached page codes AND per-page scale byte-identical, and the
    extension must decode exactly like a cold serve."""
    cfg = _qwen()
    rng = np.random.default_rng(11)
    donor = rng.integers(1, 250, size=13).tolist()
    eng = Engine(cfg, max_batch=1, max_len=64, prefill_buckets=(16, 32),
                 prefix_cache=True, attn=I8)
    eng.submit(Request(0, donor, max_new_tokens=3))
    eng.run()
    matched = eng.prefix.match(donor[:12])
    tail = matched[-1]
    eng.pages.allocator.unref(matched)     # match refs for the caller
    before_k = np.asarray(eng.pages.cache["k_pages"][:, tail])
    before_s = np.asarray(eng.pages.cache["k_scale"][:, tail])
    assert before_k.dtype == np.int8

    eng.submit(Request(1, donor[:12], max_new_tokens=3))   # full hit
    res = eng.run()
    assert eng.summary()["cow_copies"] == 1
    np.testing.assert_array_equal(
        before_k, np.asarray(eng.pages.cache["k_pages"][:, tail]))
    np.testing.assert_array_equal(
        before_s, np.asarray(eng.pages.cache["k_scale"][:, tail]))

    solo = Engine(cfg, params=eng.params, max_batch=1, max_len=64,
                  prefill_buckets=(16, 32), prefix_cache=False, attn=I8)
    solo.submit(Request(9, donor[:12], max_new_tokens=3))
    assert res[1].tokens == solo.run()[9].tokens


def test_prefix_hit_token_identity_under_int8():
    """Hot (prefix-cache) and cold serves are token-identical on the
    int8 pool: prefill round-trips K/V through the pool grid before the
    write, so hits gather exactly what cold prefill would recompute."""
    cfg = _qwen()
    rng = np.random.default_rng(3)
    shared = rng.integers(1, 250, size=20).tolist()
    prompts = [shared + rng.integers(1, 250, size=5 + i).tolist()
               for i in range(3)] + [shared[:6], shared[:12]]

    def serve(params, prefix):
        eng = Engine(cfg, params=params, max_batch=2, max_len=64,
                     prefill_buckets=(16, 32), prefix_cache=prefix, attn=I8)
        for uid, p in enumerate(prompts):
            eng.submit(Request(uid, p, max_new_tokens=4))
        return eng, {u: r.tokens for u, r in eng.run().items()}

    e1, cold = serve(None, False)
    e2, hot = serve(e1.params, True)
    assert hot == cold, f"int8 hit tokens diverged: {hot} != {cold}"
    assert e2.summary()["prefix_hits"] > 0


def test_summary_reports_dtype_footprint():
    cfg = _qwen()
    legs = {}
    params = None
    for dt in ("int8", "fp8_v", "fp32"):
        eng = Engine(cfg, params=params, max_batch=2, max_len=64,
                     prefill_buckets=(16, 32),
                     attn=AttnSpec(kv_dtype=dt))
        params = eng.params
        for uid, p in enumerate(_prompts(3, seed=5)):
            eng.submit(Request(uid, p, max_new_tokens=3))
        eng.run()
        legs[dt] = eng.summary()
        assert legs[dt]["kv_dtype"] == dt
        assert legs[dt]["cache_bytes_per_token"] > 0
    for dt in ("int8", "fp8_v"):
        ratio = legs[dt]["cache_bytes_per_token"] \
            / legs["fp32"]["cache_bytes_per_token"]
        assert ratio <= 0.35, \
            f"{dt} pool is x{ratio:.2f} of fp32 bytes/token (> 0.35)"


# --------------------------------------------------- absmax calibration
def test_absmax_roundtrip_beats_grid():
    """Per-page absmax calibration: on small-magnitude pages the static
    power-of-two grid wastes most of its code range; the calibrated
    scale round-trips within its own half step and far below the grid's
    error."""
    from repro.core.quant import absmax_page_scale, encode_pool_scaled

    rng = np.random.default_rng(17)
    ib = 4
    s0 = pool_scale(ib)
    x = jnp.asarray(rng.normal(0, 0.05, size=(6, 4, 2, 8))
                    .astype(np.float32))
    ks = absmax_page_scale(x, ib)                       # [P, N]
    assert ks.shape == (6, 2)
    codes = encode_pool_scaled(x, ks[:, None, :, None])
    assert int(codes.min()) >= -127, "calibrated encode emitted POISON_CODE"
    dq = np.asarray(codes, np.float32) * np.asarray(ks)[:, None, :, None]
    err_absmax = np.abs(dq - np.asarray(x)).max()
    assert err_absmax <= float(np.asarray(ks).max()) / 2 * (1 + 1e-6), \
        "calibrated round-trip exceeded its half-step bound"
    dq_grid = np.asarray(decode_pool(encode_pool(x, ib), s0))
    err_grid = np.abs(dq_grid - np.asarray(x)).max()
    assert err_absmax < err_grid / 4, \
        (f"absmax error {err_absmax:.5f} not clearly below the static "
         f"grid's {err_grid:.5f}")


def test_absmax_zero_page_falls_back_to_grid_scale():
    """An all-zero page has no absmax: the scale falls back to the
    static grid step (finite — a 0 scale would poison the dequant)."""
    from repro.core.quant import absmax_page_scale

    s = np.asarray(absmax_page_scale(jnp.zeros((2, 4, 2, 8), F32), 4))
    assert (s == pool_scale(4)).all()


def test_absmax_spec_and_defaults():
    """kv_scale is opt-in: defaults stay on the bit-parity grid, bad
    values and the fp32+absmax combination are rejected up front."""
    assert AttnSpec().kv_scale == "grid"
    assert AttnSpec(kv_dtype="int8").kv_scale == "grid"
    with pytest.raises(ValueError):
        AttnSpec(kv_scale="per_tensor")
    with pytest.raises(ValueError, match="absmax"):
        Engine(_qwen(), max_batch=1, max_len=32,
               attn=AttnSpec(kv_dtype="fp32", kv_scale="absmax"))


def test_absmax_decode_drift_gate_vs_fp32_oracle():
    """fp32 A/B drift gate: with pruning off (pure quantization A/B),
    the absmax pool's decode output drifts from the fp32 oracle by less
    than the static-grid pool does, and stays within a 10% bound of the
    oracle's output range."""
    from repro.core.quant import absmax_page_scale, encode_pool_scaled

    rng = jax.random.PRNGKey(5)
    B, N, G, hd, ps, nP = 2, 2, 2, 8, 4, 8
    P = 1 + B * nP
    Sk = nP * ps
    hdp = HDPConfig(block_q=1, block_k=ps, rho_b=0.99, causal=True,
                    head_pruning=False, calib="none")
    ib = hdp.int_bits
    # small-magnitude K/V: the regime where calibration matters
    ks = 0.05 * jax.random.normal(jax.random.fold_in(rng, 1),
                                  (P, N, ps, hd), F32)
    vs = 0.05 * jax.random.normal(jax.random.fold_in(rng, 2),
                                  (P, N, ps, hd), F32)
    q = jax.random.normal(jax.random.fold_in(rng, 3), (B, N, G, 1, hd), F32)
    table = jnp.arange(1, P, dtype=jnp.int32).reshape(B, nP)
    pos = jnp.full((B, 1), Sk - 1, jnp.int32)
    q_pos = pos[:, None, None, :]
    ar = jnp.arange(Sk)
    k_pos = jnp.where(ar[None] <= pos, ar, -1)[:, None, None, :]
    kw = dict(q_pos=q_pos, k_pos=k_pos, hdp=hdp, stage3="xla",
              page_chunk=128)

    out_fp, _ = hdp_paged_decode_attention(
        q, ks, vs, scout_int8(ks, hdp), table, **kw)

    # head-major pools: absmax over a page's [ps, hd] per kv head
    ksc = absmax_page_scale(jnp.swapaxes(ks, 1, 2), ib)
    vsc = absmax_page_scale(jnp.swapaxes(vs, 1, 2), ib)
    out_a, _ = hdp_paged_decode_attention(
        q, encode_pool_scaled(ks, ksc[:, :, None, None]),
        encode_pool_scaled(vs, vsc[:, :, None, None]), None, table,
        k_scale=ksc, v_scale=vsc, kv_scale="absmax", **kw)

    g = jnp.full((P, N), pool_scale(ib), F32)
    out_g, _ = hdp_paged_decode_attention(
        q, encode_pool(ks, ib), encode_pool(vs, ib), None, table,
        k_scale=g, v_scale=g, **kw)

    ref = np.asarray(out_fp)
    assert np.isfinite(np.asarray(out_a)).all()
    drift_a = np.abs(np.asarray(out_a) - ref).max()
    drift_g = np.abs(np.asarray(out_g) - ref).max()
    assert drift_a < drift_g, \
        (f"absmax drift {drift_a:.5f} not below the static grid's "
         f"{drift_g:.5f} vs the fp32 oracle")
    assert drift_a <= 0.1 * np.abs(ref).max() + 1e-6, \
        f"absmax drift {drift_a:.5f} breaches the 10% oracle gate"


def test_absmax_serving_end_to_end():
    """The calibrated pool serves: full generations, deterministic under
    the FIXED format, kv_scale surfaced in the summary. (Byte-identity
    vs the grid pool is NOT asserted — absmax forfeits the write-time
    bit-parity by design; the drift gate above is its contract.)"""
    cfg = _qwen()
    prompts = _prompts(3, seed=19)
    a8 = AttnSpec(kv_dtype="int8", kv_scale="absmax")

    def serve(params):
        eng = Engine(cfg, params=params, max_batch=2, max_len=64,
                     prefill_buckets=(16, 32), attn=a8)
        for uid, p in enumerate(prompts):
            eng.submit(Request(uid, p, max_new_tokens=4))
        return eng, {u: r.tokens for u, r in eng.run().items()}

    eng, r1 = serve(None)
    assert all(len(t) == 4 for t in r1.values())
    assert eng.summary()["kv_scale"] == "absmax"
    assert eng.summary()["kv_dtype"] == "int8"
    _, r2 = serve(eng.params)
    assert r2 == r1, "absmax serving is not deterministic"


# ------------------------------------------------------------ kernel route
@pytest.mark.slow  # interpret-mode kernel per layer per step
def test_pallas_backend_matches_xla_under_int8():
    cfg = _qwen()
    prompts = _prompts(2, seed=11)
    eng, xla = None, None
    res = {}
    for backend in ("xla", "pallas"):
        e = Engine(cfg, params=eng.params if eng else None, max_batch=2,
                   max_len=64, prefill_buckets=(16, 32),
                   attn=AttnSpec(backend=backend, kv_dtype="int8"))
        eng = eng or e
        for uid, p in enumerate(prompts):
            e.submit(Request(uid, p, max_new_tokens=4))
        res[backend] = {u: r.tokens for u, r in e.run().items()}
    assert res["xla"] == res["pallas"]
