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


# The paged scout at qwen2-1.5b's widths (2 kv heads x 6 query heads,
# head_dim 128, 128-token pages) over a layer-stacked int8 pool. Nine
# table columns give ppb = 3 columns a block, so a full slot walks three
# blocks; the slots' extents are each case's.
SCOUT_COLS, SCOUT_L, SCOUT_LAYER = 9, 2, 1
SCOUT_LENS = {
    "partial_page": (9 * 128 - 5, 4 * 128 + 1, 100, 7 * 128 + 64, 383),
    "empty_slot": (0, 5 * 128 + 3, 0, 0, 9 * 128),
    "page_boundary": (128, 3 * 128, 6 * 128, 9 * 128, 256),
    "scratch_columns": (2 * 128 + 7, 0, 8 * 128 + 1, 128, 4 * 128),
    "extreme_codes": (9 * 128, 9 * 128 - 1, 5 * 128, 3 * 128 + 9, 1),
    "poison_allocated": (9 * 128 - 5, 4 * 128 + 1, 100, 7 * 128 + 64, 383),
    "poison_past_count": (2 * 128 + 7, 0, 8 * 128 + 1, 128, 4 * 128),
}


def _scout_case(case, seed=0):
    """A layer-stacked int8 pool, a page table and one decode query per
    slot, with ``SCOUT_LENS[case]`` valid positions a slot."""
    from repro.models.attention import _fixed_split

    lens = np.asarray(SCOUT_LENS[case])
    B, N, G, hd, ps, nP = len(lens), 2, 6, 128, 128, SCOUT_COLS
    P = 1 + B * nP
    rng = np.random.default_rng(seed)
    pool = rng.integers(-127, 128, size=(SCOUT_L, P, N, ps, hd), dtype=np.int8)
    q = rnd(B, N, G, 1, hd, seed=seed + 1, scale=6.0)
    if case == "extreme_codes":
        pool = np.where(rng.random(pool.shape) < 0.5, -127, 127).astype(np.int8)
        q = 20.0 * jnp.sign(q)        # integer parts -16 and 15
    table = rng.permutation(np.arange(1, P, dtype=np.int32)).reshape(B, nP)
    counts = -(-lens // ps)
    if case in ("scratch_columns", "poison_past_count"):
        # columns past the count are unallocated: they name scratch page 0
        table[np.arange(nP)[None] >= counts[:, None]] = 0
    hdp = HDPConfig(block_q=1, block_k=ps, rho_b=0.3, causal=True,
                    head_pruning=True, normalize_head_score=True,
                    calib="none")
    _, iq, _ = _fixed_split(q, hdp)
    q_pos = jnp.asarray(lens - 1, jnp.int32)[:, None, None, None]
    return dict(pool=pool, table=table, iq=iq, q_pos=q_pos, lens=lens,
                counts=counts, hdp=hdp, ps=ps)


def _xla_scout(c, pool):
    """The XLA scout of the int8 pool: gather every column, widen,
    truncate, multiply, pool and decide (``decode_scout``)."""
    from repro.core.hdp import decode_scout
    from repro.core.quant import pool_view_finite
    from repro.models.attention import _mask_bias, gather_pages

    iq, hdp = c["iq"], c["hdp"]
    B, N, G, _, hd = iq.shape
    nP = c["table"].shape[1]
    k = pool_view_finite(gather_pages(jnp.asarray(pool), c["table"],
                                      SCOUT_LAYER), hdp.int_bits)
    ik = jnp.trunc(k.reshape(B, nP * c["ps"], N, hd))
    s = jnp.einsum("bngqh,bsnh->bngqs", iq, ik)
    ar = jnp.arange(nP * c["ps"])
    k_pos = jnp.where(ar[None] <= c["q_pos"].reshape(B, 1), ar, -1)
    valid = _mask_bias(c["q_pos"], k_pos[:, None, None, :], True, 0)
    return decode_scout(s, valid, hdp)


def _kernel_scout(c, pool):
    """The paged scout kernel and the shared decision on its theta."""
    from repro.core.hdp import scout_decision
    from repro.kernels.hdp_paged_scout import hdp_paged_scout

    lens = jnp.asarray(c["lens"], jnp.int32)
    theta, walked = hdp_paged_scout(
        c["iq"][..., 0, :], jnp.asarray(pool), jnp.asarray(c["table"]), lens,
        int_bits=c["hdp"].int_bits, layer=jnp.int32(SCOUT_LAYER),
        interpret=True)
    bvalid = (jnp.arange(SCOUT_COLS) * c["ps"] < lens[:, None])[:, None, None]
    return scout_decision(theta, bvalid, lens[:, None, None], c["hdp"]), walked


class TestPagedScoutKernel:
    @pytest.mark.parametrize("case", list(SCOUT_LENS))
    def test_matches_xla_scout(self, case):
        """Theta, the keep mask and the head gate equal the XLA scout's
        bit for bit; the kernel walks ceil(kv_len / ps) columns a slot.
        Poison codes in allocated pages read 0 at valid positions and
        change nothing past a slot's extent; poisoned pages past the
        count (the scratch page among them) change nothing."""
        c = _scout_case(case)
        pool = c["pool"]
        if case == "poison_allocated":
            ps, table = c["ps"], c["table"]
            hit = np.zeros(pool.shape[1:], bool)          # [P, N, ps, hd]
            for b, n in enumerate(c["lens"]):
                last = table[b, max(int(c["counts"][b]) - 1, 0)]
                hit[last, :, n % ps:] = True             # the tail past kv_len
                hit[table[b, 0], 1, 3, :5] = n > 3       # valid positions
            poisoned = pool.copy()
            poisoned[SCOUT_LAYER][hit] = POISON_CODE
            clean = pool.copy()
            clean[SCOUT_LAYER][hit] = 0
        elif case == "poison_past_count":
            dead = [0] + [int(p) for b, n in enumerate(c["counts"])
                          for p in c["table"][b, n:] if p]
            poisoned = pool.copy()
            poisoned[SCOUT_LAYER, dead] = POISON_CODE
            clean = pool
        else:
            poisoned = clean = pool
        # gate about half the heads: tau_h at the median head importance
        tau = float(np.median(np.asarray(_xla_scout(c, clean)[3])))
        c["hdp"] = c["hdp"].replace(tau_h=tau)
        (keep, bvalid, theta, theta_head, head_kept), walked = \
            _kernel_scout(c, poisoned)
        want = _xla_scout(c, poisoned)
        names = ("keep", "bvalid", "theta", "theta_head", "head_kept")
        for name, got, ref in zip(names, (keep, bvalid, theta, theta_head,
                                          head_kept), want):
            np.testing.assert_array_equal(
                np.broadcast_to(np.asarray(got), np.shape(ref)),
                np.asarray(ref), err_msg=name)
        np.testing.assert_array_equal(np.asarray(walked), c["counts"])
        theta = np.asarray(theta)
        past = np.arange(SCOUT_COLS)[None] >= c["counts"][:, None]
        assert (theta.transpose(0, 3, 1, 2)[past] == 0).all()
        assert (theta.transpose(0, 3, 1, 2)[~past] > 0).all()
        assert 0 < np.asarray(head_kept).sum() < head_kept.size
        assert 0 < np.asarray(keep).sum() < np.asarray(bvalid).sum() * 12
        if poisoned is not clean:
            np.testing.assert_array_equal(
                theta, np.asarray(_kernel_scout(c, clean)[0][2]))
