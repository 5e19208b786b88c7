"""Per-kernel validation: shape/dtype sweeps, interpret mode vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HDPConfig, hdp_attention
from repro.core.quant import POISON_CODE, quantize_fixed
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.hdp_block_attn import hdp_block_sparse_attention
from repro.kernels.hdp_scout import hdp_scout
from repro.kernels.ops import hdp_attention_tpu


def rnd(*shape, seed=0, scale=2.0, dtype=jnp.float32):
    x = scale * jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


# ------------------------------------------------------------------ flash
class TestFlashKernel:
    @pytest.mark.parametrize("shape", [
        (1, 2, 128, 64),
        pytest.param((2, 3, 256, 128), marks=pytest.mark.slow),
        pytest.param((1, 1, 160, 64), marks=pytest.mark.slow),  # ragged S
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, shape, causal):
        q, k, v = (rnd(*shape, seed=s) for s in (1, 2, 3))
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q, k, v = (rnd(1, 2, 128, 64, seed=s, dtype=dtype) for s in (4, 5, 6))
        out = flash_attention(q, k, v, causal=True, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol)


# ------------------------------------------------------------------ scout
class TestScoutKernel:
    @pytest.mark.parametrize("shape", [
        (1, 2, 128, 64),
        pytest.param((2, 2, 256, 32), marks=pytest.mark.slow),
    ])
    @pytest.mark.parametrize("rho", [0.5, -0.5])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, shape, rho, causal):
        iq = jnp.trunc(rnd(*shape, seed=7, scale=3.0))
        ik = jnp.trunc(rnd(*shape, seed=8, scale=3.0))
        theta, keep, th_head = hdp_scout(
            iq, ik, rho_b=rho, block_q=64, block_k=64, causal=causal,
            interpret=True)
        theta_r, keep_r, th_head_r = ref.hdp_scout_ref(
            iq, ik, block_q=64, block_k=64, rho_b=rho, causal=causal)
        np.testing.assert_allclose(np.asarray(theta), np.asarray(theta_r),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(keep), np.asarray(keep_r))
        np.testing.assert_allclose(np.asarray(th_head),
                                   np.asarray(th_head_r), rtol=1e-5)

    def test_chunked_kv_equals_single_chunk(self):
        iq = jnp.trunc(rnd(1, 1, 256, 64, seed=9, scale=3.0))
        ik = jnp.trunc(rnd(1, 1, 256, 64, seed=10, scale=3.0))
        a = hdp_scout(iq, ik, rho_b=0.5, block_q=64, block_k=64,
                      chunk_blocks=1, interpret=True)
        b = hdp_scout(iq, ik, rho_b=0.5, block_q=64, block_k=64,
                      chunk_blocks=4, interpret=True)
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


# ------------------------------------------------------------- block attn
class TestBlockAttnKernel:
    def _mk(self, B=1, H=2, S=256, hd=64, seed=0):
        q = quantize_fixed(rnd(B, H, S, hd, seed=seed))
        k = quantize_fixed(rnd(B, H, S, hd, seed=seed + 1))
        v = rnd(B, H, S, hd, seed=seed + 2)
        return q, k, v

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("approx", [True, False])
    def test_full_keep_matches_masked_ref(self, causal, approx):
        q, k, v = self._mk(seed=11)
        nq = nk = 256 // 64
        keep = jnp.ones((1, 2, nq, nk), bool)
        theta = jnp.ones((1, 2, nq, nk))
        idx, cnt = ref.keep_mask_to_indices(keep, theta, nk)
        hk = jnp.ones((1, 2), bool)
        out = hdp_block_sparse_attention(
            q, k, v, idx, cnt, hk, causal=causal, approx=approx,
            block_q=64, block_k=64, interpret=True)
        want = ref.hdp_block_attn_ref(q, k, v, keep, block_q=64, block_k=64,
                                      causal=causal, approx=approx)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_sparse_keep_matches_ref(self):
        q, k, v = self._mk(seed=13)
        iq, ik = jnp.trunc(q), jnp.trunc(k)
        theta, keep, _ = ref.hdp_scout_ref(iq, ik, block_q=64, block_k=64,
                                           rho_b=0.5, causal=True)
        idx, cnt = ref.keep_mask_to_indices(keep, theta, keep.shape[-1])
        hk = jnp.ones((1, 2), bool)
        out = hdp_block_sparse_attention(
            q, k, v, idx, cnt, hk, causal=True, approx=True,
            block_q=64, block_k=64, interpret=True)
        want = ref.hdp_block_attn_ref(q, k, v, keep, block_q=64, block_k=64,
                                      causal=True, approx=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_head_gate_zeroes_output(self):
        q, k, v = self._mk(seed=17)
        nq = nk = 256 // 64
        keep = jnp.ones((1, 2, nq, nk), bool)
        idx, cnt = ref.keep_mask_to_indices(keep, jnp.ones_like(keep, jnp.float32), nk)
        hk = jnp.array([[True, False]])
        out = hdp_block_sparse_attention(q, k, v, idx, cnt, hk, causal=True,
                                         block_q=64, block_k=64, interpret=True)
        assert float(jnp.abs(out[0, 1]).max()) == 0.0
        assert float(jnp.abs(out[0, 0]).max()) > 0.0


# ----------------------------------------------------- end-to-end pipeline
class TestHDPPipeline:
    def test_pipeline_matches_core_hdp(self):
        """kernel pipeline == core.hdp_attention with the same TPU blocks."""
        B, H, S, hd = 1, 2, 256, 64
        q, k, v = (rnd(B, H, S, hd, seed=s) for s in (19, 20, 21))
        cfg = HDPConfig(block_q=64, block_k=64, rho_b=0.5, tau_h=0.0,
                        causal=True, normalize_head_score=True)
        out_k, stats_k = hdp_attention_tpu(q, k, v, cfg, interpret=True,
                                           return_stats=True)
        out_c, stats_c = hdp_attention(q, k, v, cfg)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_c),
                                   rtol=3e-3, atol=3e-3)
        assert abs(float(stats_k["head_sparsity"])
                   - float(stats_c.head_sparsity)) < 1e-6

    def test_max_keep_cap_degrades_gracefully(self):
        B, H, S, hd = 1, 2, 256, 64
        q, k, v = (rnd(B, H, S, hd, seed=s) for s in (22, 23, 24))
        cfg = HDPConfig(block_q=64, block_k=64, rho_b=0.5, causal=True,
                        normalize_head_score=True)
        exact, _ = hdp_attention_tpu(q, k, v, cfg, interpret=True)
        capped, _ = hdp_attention_tpu(q, k, v, cfg, max_keep=2,
                                      interpret=True)
        # capped keeps the top-theta blocks; output stays finite & close-ish
        assert bool(jnp.isfinite(capped).all())
        cos = float((exact * capped).sum() /
                    (jnp.linalg.norm(exact) * jnp.linalg.norm(capped) + 1e-9))
        assert cos > 0.8


# ------------------------------------------------- paged FUM decode kernel
# one batch holds rows of 0, 1, ppb-1, ppb, ppb+1 and every (mk) kept
# page: mk = 9 table columns give ppb = 3 pages a compute block. The
# full row comes first, so a later row's partial last block lands on the
# double buffer that holds the full row's last page.
PAGED_MK = 9
PAGED_COUNTS = (9, 1, 2, 0, 3, 4)


def _paged_case(pool_dtype, sq, seed=0):
    """Stage-3 inputs over a page pool: per-head, per-row keep of each
    row's ``PAGED_COUNTS`` fetched pages (row 0 keeps every page for
    every head and row), extents ending inside each row's last page."""
    from repro.core.quant import encode_pool, pool_scale
    from repro.kernels.hdp_paged_decode import pages_per_block
    from repro.models.attention import _fixed_split

    B, N, G, hd, ps, nP = len(PAGED_COUNTS), 2, 2, 8, 4, PAGED_MK
    P = 1 + B * nP
    rng = np.random.default_rng(seed)
    hdp = HDPConfig(block_q=1, block_k=ps, rho_b=0.5, causal=True,
                    head_pruning=False, calib="none")
    table = np.arange(1, P, dtype=np.int32).reshape(B, nP)
    fetched = np.zeros((B, nP), bool)
    for b, c in enumerate(PAGED_COUNTS):
        fetched[b, rng.choice(nP, size=c, replace=False)] = True
    keep = (rng.random((B, N, G, sq, nP)) < 0.6) & fetched[:, None, None, None]
    keep[:, 0, 0, 0] |= fetched
    keep[0] = True
    q = rnd(B, N, G, sq, hd, seed=seed + 1)
    ks = rnd(P, N, ps, hd, seed=seed + 2)
    vs = rnd(P, N, ps, hd, seed=seed + 3)
    kw = {}
    if pool_dtype == "int8":
        ks, vs = encode_pool(ks, hdp.int_bits), encode_pool(vs, hdp.int_bits)
        s0 = jnp.full((P, N), pool_scale(hdp.int_bits), jnp.float32)
        kw = dict(k_scale=s0, v_scale=s0)
    else:
        ks, vs = ks.astype(pool_dtype), vs.astype(pool_dtype)
    assert pages_per_block(nP, ks, vs) == 3
    base = rng.integers((nP - 1) * ps, nP * ps - sq + 1, size=B)
    q_pos = jnp.asarray(base[:, None] + np.arange(sq), jnp.int32)[:, None, None]
    k_pos = jnp.arange(nP * ps, dtype=jnp.int32)[None, None, None]
    qq, _, fq = _fixed_split(q, hdp)
    return dict(qq=qq, fq=fq, k=ks, v=vs, table=jnp.asarray(table),
                keep=jnp.asarray(keep), fetched=jnp.asarray(fetched),
                q_pos=q_pos, k_pos=k_pos, hdp=hdp, ps=ps, **kw)


def _paged_kernel(c, **over):
    from repro.models.attention import _paged_fum_kernel_stage3
    c = {**c, **over}
    B, N, G = c["qq"].shape[:3]
    return _paged_fum_kernel_stage3(
        c["qq"], c["k"], c["v"], c["table"], c["keep"],
        jnp.ones((B, N, G), bool), c["q_pos"], c["fetched"], hdp=c["hdp"],
        ps=c["ps"], k_scale=c.get("k_scale"), v_scale=c.get("v_scale"))


PAGED_POOLS = [("int8", 1e-5), ("float32", 1e-5), ("bfloat16", 2e-2)]


class TestPagedFumKernel:
    @pytest.mark.parametrize("sq", [1, 4])
    @pytest.mark.parametrize("pool_dtype,tol", PAGED_POOLS)
    def test_matches_xla_stage3(self, pool_dtype, tol, sq):
        """The block walk against the XLA stage 3 (the page-chunk scan in
        one chunk) on the same fetched pages, keep masks and extents."""
        from repro.models.attention import _mask_bias, _paged_scan_attention
        c = _paged_case(pool_dtype, sq)
        out = _paged_kernel(c)
        B, N, G, _, hd = c["qq"].shape
        want = _paged_scan_attention(
            c["qq"], c["fq"], c["k"], c["v"],
            jnp.where(c["fetched"], c["table"], 0), c["keep"],
            _mask_bias(c["q_pos"], c["k_pos"], True, 0),
            jnp.ones((B, N, G), bool), hdp=c["hdp"], ps=c["ps"],
            cpp=PAGED_MK, scale=1.0 / hd ** 0.5, k_scale=c.get("k_scale"),
            v_scale=c.get("v_scale"))
        assert bool(jnp.isfinite(out).all())
        np.testing.assert_array_equal(np.asarray(out[3]), 0.0)   # no page
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("sq", [1, 4])
    @pytest.mark.parametrize("pool_dtype", [p for p, _ in PAGED_POOLS])
    def test_poison(self, pool_dtype, sq):
        """Pruned pages poisoned through every channel (NaN / sentinel
        codes, NaN scales) leave every output bit unchanged; a poisoned
        kept page trips NaN in its row alone — the rows after it whose
        last block reuses its buffer stay exact."""
        c = _paged_case(pool_dtype, sq)
        clean = np.asarray(_paged_kernel(c))
        table, fetched = np.asarray(c["table"]), np.asarray(c["fetched"])
        pruned = jnp.asarray(table[~fetched])
        kept = int(table[0, -1])              # row 0's last page, kept

        def poison(pages):
            if pool_dtype == "int8":
                return dict(
                    k=c["k"].at[pages].set(POISON_CODE),
                    v=c["v"].at[pages].set(POISON_CODE),
                    k_scale=c["k_scale"].at[pages].set(jnp.nan),
                    v_scale=c["v_scale"].at[pages].set(jnp.nan))
            return dict(k=c["k"].at[pages].set(jnp.nan),
                        v=c["v"].at[pages].set(jnp.nan))

        np.testing.assert_array_equal(
            np.asarray(_paged_kernel(c, **poison(pruned))), clean)
        bad = np.asarray(_paged_kernel(c, **poison(kept)))
        assert np.isnan(bad[0]).all(), "a poisoned kept page did not trip"
        np.testing.assert_array_equal(bad[1:], clean[1:])
