"""Cost-driven autotune subsystem: predictor, tuner, adaptive speculation.

Three contracts are load-bearing:

* the analytic predictor agrees with the while-aware HLO cost model on
  compiled attention jits — absolute FLOPs within a small factor, kv_len
  *scaling* tight (the ranking signal the tuner actually uses);
* cost-policy selection is deterministic and byte-identical to static
  selection end-to-end through the serving engine (off-TPU the cost
  model must rank the same winners the static priority order picks);
* acceptance-adaptive speculation stays byte-identical to greedy decode
  at ANY forced draft-length schedule, including k=1 (speculation off).
"""
from __future__ import annotations

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import AttnCall, AttnSpec, DraftProfile, attention
from repro.attention.registry import (BACKEND_ENV, POLICY_ENV,
                                      effective_policy, resolve_backend)
from repro.autotune import (CallSig, SparsityEstimate, SpecConfig,
                            SpecController, Tuner, call_signature,
                            crossover_table, predict, predict_engine_step,
                            reset_default_tuner)
from repro.autotune.tuner import TUNER_CACHE_ENV, default_tuner
from repro.configs import get_config
from repro.configs.base import reduced
from repro.core.config import HDPConfig
from repro.roofline import hlo_cost
from repro.roofline.hardware import (HOST_CPU, TPU_V5E, detect_profile,
                                     get_profile)
from repro.serving import Engine, Request

B, N, G, HD = 1, 2, 2, 8
HDP = HDPConfig(block_q=4, block_k=4, rho_b=0.5, tau_h=0.0,
                normalize_head_score=True, calib="max")


@pytest.fixture(autouse=True)
def _fresh_default_tuner():
    """Process-default tuner state must not leak between tests."""
    reset_default_tuner()
    yield
    reset_default_tuner()


def _decode_sig(kv=256, hdp=False, **kw):
    base = dict(mode="decode", layout="dense", batch=B, n_kv_heads=N,
                group=G, sq=1, hd=HD, kv_len=kv, hdp=hdp)
    if hdp:
        base.update(block_q=4, block_k=4)
    base.update(kw)
    return CallSig(**base)


# --------------------------------------------------------------- hardware
class TestHardware:
    def test_get_profile(self):
        assert get_profile("tpu_v5e") is TPU_V5E
        assert get_profile("host_cpu") is HOST_CPU
        with pytest.raises(KeyError):
            get_profile("h100")

    def test_detect_profile_matches_backend(self):
        prof = detect_profile()
        expect = TPU_V5E if jax.default_backend() == "tpu" else HOST_CPU
        assert prof is expect

    @pytest.mark.parametrize("kind,expect", [("TPU v5 lite", TPU_V5E),
                                             ("TPU v4", None),
                                             ("NVIDIA H100", None)])
    def test_detect_profile_keys_on_device_kind(self, monkeypatch, kind,
                                                expect):
        class Dev:
            platform = "gpu" if kind.startswith("NVIDIA") else "tpu"
            device_kind = kind

        monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
        if expect is None:
            with pytest.raises(RuntimeError, match="no hardware profile"):
                detect_profile()
        else:
            assert detect_profile() is expect

    def test_analysis_reexports_tpu_constants(self):
        from repro.roofline import analysis
        assert analysis.PEAK_FLOPS == TPU_V5E.peak_flops
        assert analysis.HBM_BW == TPU_V5E.hbm_bw
        assert analysis.ICI_BW == TPU_V5E.ici_bw
        assert analysis.HBM_BYTES == TPU_V5E.mem_bytes

    def test_analyze_takes_profile(self):
        from repro.roofline import analysis
        x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        compiled = jax.jit(lambda a, b: a @ b).lower(x, x).compile()
        r_tpu = analysis.analyze(compiled)
        r_cpu = analysis.analyze(compiled, hw=HOST_CPU)
        assert r_tpu.hw == "tpu_v5e" and r_cpu.hw == "host_cpu"
        assert r_cpu.compute_t > r_tpu.compute_t  # slower envelope
        assert r_cpu.flops == r_tpu.flops         # counts are hw-free


# ---------------------------------------------------------------- CallSig
class TestCallSig:
    def test_dense_signature_from_live_shapes(self):
        call = AttnCall(mode="decode", layout="dense")
        q = jnp.zeros((B, N, G, 1, HD), jnp.float32)
        k = jnp.zeros((B, 32, N, HD), jnp.float32)
        sig = call_signature(call, q, k=k)
        assert (sig.batch, sig.n_kv_heads, sig.group) == (B, N, G)
        assert (sig.sq, sig.kv_len, sig.hd) == (1, 32, HD)
        assert sig.heads == N * G
        assert not sig.hdp and sig.page_size == 0

    def test_paged_signature_derives_extent_from_table(self):
        call = AttnCall(mode="decode", layout="paged", hdp=HDP,
                        per_slot=True)
        q = jnp.zeros((B, N, G, 1, HD), jnp.float32)
        cache = {"k_pages": jnp.zeros((9, N, 4, HD), jnp.float32)}
        table = jnp.ones((B, 6), jnp.int32)
        sig = call_signature(call, q, cache=cache, page_table=table)
        assert sig.kv_len == 6 * 4 and sig.page_size == 4
        assert sig.hdp and (sig.block_q, sig.block_k) == (4, 4)
        assert sig.per_slot

    def test_key_distinguishes_and_roundtrips(self):
        a, b = _decode_sig(kv=128), _decode_sig(kv=256)
        assert a.key() != b.key()
        assert a.key() == _decode_sig(kv=128).key()
        assert isinstance(hash(a), int)  # usable as a dict key directly


# -------------------------------------------------------------- predictor
class TestPredict:
    def test_monotonic_in_kv_len(self):
        ts = [predict("xla_dense", _decode_sig(kv=kv),
                      HOST_CPU).step_time(HOST_CPU)
              for kv in (128, 512, 2048)]
        assert ts[0] < ts[1] < ts[2]

    def test_dense_hdp_costs_more_than_dense(self):
        # dense-layout HDP streams every byte AND quantizes: pruning can
        # only win on the paged fetch-upon-mask path
        sig = _decode_sig(kv=1024, hdp=True)
        t_hdp = predict("xla_hdp", sig, TPU_V5E).step_time(TPU_V5E)
        t_dense = predict("xla_dense", _decode_sig(kv=1024),
                          TPU_V5E).step_time(TPU_V5E)
        assert t_hdp > t_dense

    def test_sparsity_shrinks_paged_hdp_bytes(self):
        sig = _decode_sig(kv=4096, hdp=True, layout="paged", page_size=16,
                          per_slot=True)
        lo = predict("paged_hdp_decode", sig, TPU_V5E,
                     SparsityEstimate(page=0.0))
        hi = predict("paged_hdp_decode", sig, TPU_V5E,
                     SparsityEstimate(page=0.9))
        assert hi.hbm_bytes < lo.hbm_bytes
        assert hi.step_time(TPU_V5E) < lo.step_time(TPU_V5E)

    def test_interpreted_pallas_never_wins_off_tpu(self):
        sig = _decode_sig(kv=4096)
        t_pallas = predict("pallas_flash", sig, HOST_CPU)
        t_dense = predict("xla_dense", sig, HOST_CPU)
        assert t_pallas.interpreted and not t_dense.interpreted
        assert t_pallas.step_time(HOST_CPU) > t_dense.step_time(HOST_CPU)
        # ...but natively compiled Pallas is competitive on TPU
        assert not predict("pallas_flash", sig, TPU_V5E).interpreted

    def test_prior_and_clamp(self):
        assert SparsityEstimate.prior(_decode_sig()) == SparsityEstimate()
        p = SparsityEstimate.prior(_decode_sig(hdp=True))
        assert p.block > 0 and p.page > 0
        c = SparsityEstimate(block=1.5, head=-0.3, page=0.5).clamped()
        assert c.block == 0.999 and c.head == 0.0 and c.page == 0.5

    def test_engine_step_dominated_by_weights(self):
        est = predict("xla_dense", _decode_sig(kv=256), TPU_V5E)
        t = predict_engine_step(1_000_000_000, 4, 24, est, TPU_V5E)
        assert t > 1_000_000_000 * 4 / TPU_V5E.hbm_bw  # weight-read floor
        assert t > 24 * est.step_time(TPU_V5E)


# ------------------------------------------------- predictor vs HLO cost
class TestHloAgreement:
    """The analytic model vs the compiled-program cost model.

    Absolute agreement is loose (XLA fuses, pads and re-materializes),
    but the kv_len *scaling* — the signal backend ranking rides on —
    must be tight.
    """

    SPEC = AttnSpec(backend="xla_dense", policy="static")

    def _compiled_cost(self, kv, sq=1):
        call = AttnCall(mode="decode" if sq == 1 else "prefill",
                        layout="dense")
        q = jnp.zeros((B, N, G, sq, HD), jnp.float32)
        k = jnp.zeros((B, kv, N, HD), jnp.float32)
        v = jnp.zeros((B, kv, N, HD), jnp.float32)
        fn = jax.jit(lambda q, k, v: attention(q, k, v, call,
                                               spec=self.SPEC)[0])
        compiled = fn.lower(q, k, v).compile()
        return hlo_cost.module_cost(compiled.as_text())

    def test_decode_flops_within_factor(self):
        for kv in (128, 512):
            hlo = self._compiled_cost(kv)
            est = predict("xla_dense", _decode_sig(kv=kv), HOST_CPU)
            assert est.flops / hlo.flops < 4.0, (kv, est.flops, hlo.flops)
            assert hlo.flops / est.flops < 4.0, (kv, est.flops, hlo.flops)
            assert est.hbm_bytes / hlo.bytes < 8.0
            assert hlo.bytes / est.hbm_bytes < 8.0

    def test_decode_kv_scaling_tight(self):
        hlo_ratio = self._compiled_cost(512).flops / \
            self._compiled_cost(128).flops
        pred_ratio = predict("xla_dense", _decode_sig(kv=512),
                             HOST_CPU).flops / \
            predict("xla_dense", _decode_sig(kv=128), HOST_CPU).flops
        assert 0.6 < hlo_ratio / pred_ratio < 1.6, (hlo_ratio, pred_ratio)

    def test_prefill_flops_within_factor(self):
        kv = 64
        hlo = self._compiled_cost(kv, sq=kv)
        sig = _decode_sig(kv=kv, mode="prefill", sq=kv)
        est = predict("xla_dense", sig, HOST_CPU)
        # predictor prices the causal triangle (kv/2); XLA computes the
        # full rectangle then masks — expect ~2x, gate at 4x
        assert est.flops / hlo.flops < 4.0
        assert hlo.flops / est.flops < 4.0


# -------------------------------------------------------------- crossover
class TestCrossover:
    SIG = CallSig(mode="decode", layout="paged", batch=4, n_kv_heads=2,
                  group=6, sq=1, hd=64, kv_len=0, page_size=16, hdp=True,
                  block_q=4, block_k=4, per_slot=True)

    def test_table_shape_and_fields(self):
        rows = crossover_table(self.SIG, TPU_V5E, (128, 8192), (0.0, 0.75))
        assert len(rows) == 4
        for r in rows:
            assert {"kv_len", "page_sparsity", "t_hdp_s", "t_dense_s",
                    "winner"} <= set(r)
            assert r["winner"] in ("hdp", "dense")

    def test_winner_flips_with_sparsity_times_kv(self):
        rows = crossover_table(self.SIG, TPU_V5E,
                               (128, 65536), (0.0, 0.9))
        by = {(r["kv_len"], r["page_sparsity"]): r["winner"] for r in rows}
        # short + dense-ish: the sparse pipeline's overhead loses
        assert by[(128, 0.0)] == "dense"
        # long + very sparse: fetch-upon-mask wins
        assert by[(65536, 0.9)] == "hdp"


# ------------------------------------------------------------------ tuner
def _cands(*names):
    return [types.SimpleNamespace(name=n) for n in names]


class TestTuner:
    CALL = AttnCall(mode="decode", layout="dense")

    def test_choose_picks_predicted_fastest(self):
        t = Tuner(hw=HOST_CPU)
        sig = _decode_sig(kv=512)
        best = t.choose(self.CALL, sig, _cands("xla_dense", "reference"))
        assert best.name == "xla_dense"  # oracle is priced out
        assert t.misses == 1 and t.hits == 0
        assert t.decision[sig.key()] == "xla_dense"
        assert not t.pending  # reference is nowhere near the margin

    def test_ambiguity_registers_pending_and_probe_flips(self):
        t = Tuner(hw=HOST_CPU, margin=1e9)  # everything is ambiguous
        sig = _decode_sig(kv=256)
        t.choose(self.CALL, sig, _cands("xla_dense", "reference"))
        assert sig.key() in t.pending
        t._probe = lambda call, sig, names: "reference"
        assert t.flush_probes() is True  # measured winner != prediction
        assert t.decision[sig.key()] == "reference"
        assert t.measured[sig.key()] == "reference"
        assert t.probes == 1 and not t.pending
        # next sighting is a measured-cache hit
        best = t.choose(self.CALL, sig, _cands("xla_dense", "reference"))
        assert best.name == "reference" and t.hits == 1

    def test_probe_failure_keeps_prediction(self):
        t = Tuner(hw=HOST_CPU, margin=1e9)
        sig = _decode_sig(kv=256)
        t.choose(self.CALL, sig, _cands("xla_dense", "reference"))

        def boom(call, sig, names):
            raise RuntimeError("probe timed out")   # transient

        t._probe = boom
        assert t.flush_probes() is False
        assert not t.pending  # never re-tried
        assert t.decision[sig.key()] == "xla_dense"
        assert t.flush_probes() is False  # idempotent when drained

    def test_probe_error_propagates(self):
        # a non-transient probe failure (e.g. a kernel the compiler
        # refuses) is a real fault: it surfaces instead of being dropped
        t = Tuner(hw=HOST_CPU, margin=1e9)
        sig = _decode_sig(kv=256)
        t.choose(self.CALL, sig, _cands("xla_dense", "reference"))

        def boom(call, sig, names):
            raise ValueError("kernel refused")

        t._probe = boom
        with pytest.raises(ValueError, match="kernel refused"):
            t.flush_probes()
        assert not t.pending  # the failed signature is not re-queued

    def test_real_probe_on_paged_hdp_call(self):
        # one end-to-end probe: synthetic inputs + jitted backend run
        call = AttnCall(mode="decode", layout="paged", hdp=HDP,
                        per_slot=True)
        sig = CallSig(mode="decode", layout="paged", batch=1, n_kv_heads=N,
                      group=G, sq=1, hd=HD, kv_len=8, page_size=4,
                      hdp=True, block_q=4, block_k=4, per_slot=True)
        t = Tuner(hw=HOST_CPU, probe_reps=1)
        assert t._probe(call, sig, ("paged_hdp_decode",)) \
            == "paged_hdp_decode"

    def test_save_load_roundtrip_warm_start(self, tmp_path):
        path = str(tmp_path / "tuner.json")
        t = Tuner(hw=HOST_CPU, margin=1e9)
        sig = _decode_sig(kv=256)
        t.choose(self.CALL, sig, _cands("xla_dense", "reference"))
        t._probe = lambda call, sig, names: "xla_dense"
        t.flush_probes()
        t.save(path)

        warm = Tuner(hw=HOST_CPU, cache_path=path)
        assert warm.measured == {sig.key(): "xla_dense"}
        warm.choose(self.CALL, sig, _cands("xla_dense", "reference"))
        assert warm.hits == 1 and warm.probes == 0 and not warm.pending

    def test_load_rejects_other_hardware(self, tmp_path):
        path = str(tmp_path / "tuner.json")
        t = Tuner(hw=HOST_CPU)
        t.measured["x"] = "xla_dense"
        t.save(path)
        other = Tuner(hw=TPU_V5E)
        assert other.load(path) is False and not other.measured

    def test_default_tuner_honors_cache_env(self, tmp_path, monkeypatch):
        path = str(tmp_path / "warm.json")
        src = Tuner()  # detected profile — what default_tuner will use
        src.measured["k"] = "xla_dense"
        src.save(path)
        monkeypatch.setenv(TUNER_CACHE_ENV, path)
        reset_default_tuner()
        assert default_tuner().measured == {"k": "xla_dense"}

    def test_decisions_deterministic_across_tuners(self):
        sigs = [_decode_sig(kv=kv, hdp=h)
                for kv in (64, 1024) for h in (False, True)]
        runs = []
        for _ in range(2):
            t = Tuner(hw=HOST_CPU)
            for sig in sigs:
                t.choose(self.CALL, sig,
                         _cands("xla_dense", "xla_hdp", "reference"))
            runs.append(dict(t.decision))
        assert runs[0] == runs[1]

    def test_decision_for_matches_phase(self):
        t = Tuner(hw=HOST_CPU)
        t.choose(self.CALL, _decode_sig(kv=512),
                 _cands("xla_dense", "reference"))
        assert t.decision_for(self.CALL) == "xla_dense"
        assert t.decision_for(AttnCall(mode="prefill",
                                       layout="dense")) is None
        name, est = t.estimate_for(self.CALL)
        assert name == "xla_dense" and est.flops > 0

    def test_sparsity_ema(self):
        t = Tuner(hw=HOST_CPU)
        t.observe_sparsity(0.4, 0.1, 0.6)
        t.observe_sparsity(0.8, 0.1, 0.2)
        sp = t.sparsity_for(_decode_sig(hdp=True))
        assert 0.4 < sp.block < 0.8 and 0.2 < sp.page < 0.6
        # non-HDP signatures never see sparsity discounts
        assert t.sparsity_for(_decode_sig()) == SparsityEstimate()


# ----------------------------------------------------------------- policy
class TestPolicy:
    def test_explicit_policy_pins(self, monkeypatch):
        monkeypatch.setenv(POLICY_ENV, "cost")
        assert effective_policy(AttnSpec(policy="static")) == "static"
        assert effective_policy(AttnSpec(policy="cost")) == "cost"

    def test_auto_policy_reads_env(self, monkeypatch):
        monkeypatch.delenv(POLICY_ENV, raising=False)
        assert effective_policy(AttnSpec()) == "static"
        monkeypatch.setenv(POLICY_ENV, "cost")
        assert effective_policy(AttnSpec()) == "cost"

    def test_invalid_policy_rejected(self):
        with pytest.raises(ValueError):
            AttnSpec(policy="fastest")

    def test_backend_env_overrides_cost_policy(self, monkeypatch):
        # REPRO_ATTN_BACKEND pins an explicit backend: the oracle CI leg
        # must win over cost ranking or it stops testing the oracle
        monkeypatch.setenv(BACKEND_ENV, "reference")
        call = AttnCall(mode="decode", layout="dense")
        b = resolve_backend(call, AttnSpec(policy="cost"),
                            sig=_decode_sig(kv=128))
        assert b.name == "reference"

    def test_cost_policy_resolves_through_tuner(self, monkeypatch):
        monkeypatch.delenv(BACKEND_ENV, raising=False)
        t = Tuner(hw=HOST_CPU)
        call = AttnCall(mode="decode", layout="dense")
        b = resolve_backend(call, AttnSpec(policy="cost"),
                            sig=_decode_sig(kv=128), tuner=t)
        assert b.name == "xla_dense"
        assert t.misses == 1  # the tuner, not the static order, answered


# ----------------------------------------------------------------- engine
def _prompts(n, lo=4, hi=20, seed=0, vocab=250):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def _run(eng, prompts, max_new=5):
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid, p, max_new_tokens=max_new))
    return {uid: r.tokens for uid, r in eng.run().items()}


class TestEngineCostPolicy:
    def test_cost_policy_token_identity_and_summary(self):
        cfg = reduced(get_config("qwen2-1.5b"))
        prompts = _prompts(4, seed=7)
        st = Engine(cfg, max_batch=2, max_len=64, prefill_buckets=(16, 32),
                    attn=AttnSpec(policy="static"))
        params = st.params
        ref = _run(st, prompts)

        co = Engine(cfg, params=params, max_batch=2, max_len=64,
                    prefill_buckets=(16, 32), attn=AttnSpec(policy="cost"))
        assert _run(co, prompts) == ref

        s = co.summary()
        assert s["attn_policy"] == "cost"
        assert {"tuner_hits", "tuner_misses", "tuner_probes",
                "tuner_cached"} <= set(s)
        assert "meas_decode_step_s" in s and s["meas_decode_step_s"] > 0
        if s["tuner_misses"]:  # skipped under REPRO_ATTN_BACKEND pins
            assert s["pred_decode_step_s"] > 0
        assert st.summary()["attn_policy"] == "static"

    def test_probe_flip_bumps_epoch_not_tokens(self, monkeypatch):
        monkeypatch.delenv(BACKEND_ENV, raising=False)
        cfg = reduced(get_config("qwen2-1.5b"))
        prompts = _prompts(3, seed=11)
        st = Engine(cfg, max_batch=2, max_len=64, prefill_buckets=(16, 32),
                    attn=AttnSpec(policy="static"))
        ref = _run(st, prompts)

        co = Engine(cfg, params=st.params, max_batch=2, max_len=64,
                    prefill_buckets=(16, 32), attn=AttnSpec(policy="cost"))
        # force "a probe flipped something" every flush: each step must
        # re-trace (epoch bump) and still commit identical tokens
        co.tuner.flush_probes = lambda: True
        assert _run(co, prompts) == ref
        assert co._attn_epoch > 0

    def test_explicit_tuner_is_installed(self):
        cfg = reduced(get_config("qwen2-1.5b"))
        mine = Tuner(hw=HOST_CPU)
        eng = Engine(cfg, max_batch=1, max_len=64, prefill_buckets=(16,),
                     attn=AttnSpec(policy="cost"), tuner=mine)
        assert eng.tuner is mine and default_tuner() is mine


class _ForcedCtl:
    """SpecController stand-in replaying a fixed (k, profile) schedule."""

    def __init__(self, ctl, ks):
        self._ctl = ctl
        self._ks = list(ks)
        self.plans = []

    def plan(self):
        k = self._ks.pop(0) if self._ks else 1
        tier = {1: self._ctl.conservative, 2: self._ctl.base}
        profile = tier.get(k, self._ctl.aggressive)
        self.plans.append(k)
        return k, profile

    def update(self, accepted, drafted):
        self._ctl.update(accepted, drafted)

    def summary(self):
        return self._ctl.summary()


class TestAdaptiveSpec:
    def test_requires_spec_decode(self):
        cfg = reduced(get_config("qwen2-1.5b"))
        with pytest.raises(ValueError, match="adaptive_spec"):
            Engine(cfg, spec_decode=False, adaptive_spec=True)

    def test_adaptive_rounds_token_identical_to_greedy(self):
        cfg = reduced(get_config("qwen2-1.5b"))
        prompts = _prompts(3, seed=5)
        base = Engine(cfg, max_batch=2, max_len=64,
                      prefill_buckets=(16, 32), spec_decode=False)
        ref = _run(base, prompts, max_new=8)

        ad = Engine(cfg, params=base.params, max_batch=2, max_len=64,
                    prefill_buckets=(16, 32), spec_decode=True,
                    draft_len=4, adaptive_spec=True)
        assert _run(ad, prompts, max_new=8) == ref
        sc = ad.spec_ctl.summary()
        assert sc["rounds"] > 0 and sc["draft_len_mean"] >= 1.0
        s = ad.summary()
        assert s["adaptive_spec"] and "acceptance_ema" in s

    @pytest.mark.parametrize("schedule", [
        [1, 1, 1, 1, 1, 1, 1, 1, 1, 1],          # speculation forced off
        [4, 1, 2, 4, 1, 3, 2, 1, 4, 2],          # thrashing k + profiles
    ])
    def test_forced_schedule_token_identity(self, schedule):
        cfg = reduced(get_config("qwen2-1.5b"))
        prompts = _prompts(2, seed=9)
        base = Engine(cfg, max_batch=2, max_len=64,
                      prefill_buckets=(16, 32), spec_decode=False)
        ref = _run(base, prompts, max_new=6)

        ad = Engine(cfg, params=base.params, max_batch=2, max_len=64,
                    prefill_buckets=(16, 32), spec_decode=True,
                    draft_len=4, adaptive_spec=True)
        forced = _ForcedCtl(ad.spec_ctl, schedule)
        ad.spec_ctl = forced
        assert _run(ad, prompts, max_new=6) == ref
        assert forced.plans[:3] == schedule[:3]


# ---------------------------------------------------------- SpecController
class TestSpecController:
    BASE = DraftProfile(scores="scout")

    def _ctl(self, **kw):
        return SpecController(self.BASE, HDP, SpecConfig(**kw))

    def test_optimistic_start_drafts_full_length(self):
        k, profile = self._ctl(k_max=4).plan()
        assert k == 4 and profile.rho_b == pytest.approx(0.6)
        assert profile.tau_h == pytest.approx(0.05)
        assert profile.scores == "scout"  # pool layout never varies

    def test_collapse_walks_down_to_k1_conservative(self):
        ctl = self._ctl(k_max=4)
        for _ in range(12):
            ctl.update(0, 3)
        assert ctl.ema < ctl.cfg.conservative_below
        k, profile = ctl.plan()
        assert k == 1
        assert profile is ctl.conservative
        assert profile.rho_b is None and profile.tau_h is None

    def test_recovery_raises_k_again(self):
        ctl = self._ctl(k_max=4)
        for _ in range(12):
            ctl.update(0, 3)
        for _ in range(20):
            ctl.update(3, 3)
        k, profile = ctl.plan()
        assert k == 4 and profile is ctl.aggressive

    def test_zero_draft_rounds_leave_ema_untouched(self):
        ctl = self._ctl()
        ema0 = ctl.ema
        ctl.update(0, 0)
        ctl.update(5, -1)
        assert ctl.ema == ema0 and ctl.rounds == 2
        assert ctl.drafted_total == 0

    def test_aggressive_rho_clamped(self):
        hot = HDP.replace(rho_b=0.93)
        ctl = SpecController(DraftProfile(), hot, SpecConfig())
        assert ctl.aggressive.rho_b == pytest.approx(0.95)

    def test_base_overrides_beat_hdp_fallback(self):
        ctl = SpecController(DraftProfile(rho_b=0.2, tau_h=0.1), HDP,
                             SpecConfig(rho_step=0.1, tau_step=0.05))
        assert ctl.aggressive.rho_b == pytest.approx(0.3)
        assert ctl.aggressive.tau_h == pytest.approx(0.15)

    def test_summary_and_rates(self):
        ctl = self._ctl()
        ctl.plan()
        ctl.update(2, 3)
        s = ctl.summary()
        assert s["rounds"] == 1 and s["drafted"] == 3 and s["accepted"] == 2
        assert s["acceptance_rate"] == pytest.approx(2 / 3)
        assert s["draft_len_mean"] >= 1.0

    def test_config_validation(self):
        with pytest.raises(ValueError):
            SpecConfig(k_min=3, k_max=2)
        with pytest.raises(ValueError):
            SpecConfig(k_min=0)
        with pytest.raises(ValueError):
            SpecConfig(beta=1.0)
