"""Hardware profiles and acceptance-adaptive speculation.

Load-bearing contracts:

* a hardware profile is picked by the device that runs, never by a
  silent default, and the roofline analysis divides by the profile it
  is handed;
* acceptance-adaptive speculation stays byte-identical to greedy decode
  at ANY forced draft-length schedule, including k=1 (speculation off).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import DraftProfile
from repro.autotune import SpecConfig, SpecController
from repro.configs import get_config
from repro.configs.base import reduced
from repro.core.config import HDPConfig
from repro.roofline.hardware import (HOST_CPU, TPU_V5E, detect_profile,
                                     get_profile)
from repro.serving import Engine, Request

HDP = HDPConfig(block_q=4, block_k=4, rho_b=0.5, tau_h=0.0,
                normalize_head_score=True, calib="max")


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


# ----------------------------------------------------------------- engine
def _prompts(n, lo=4, hi=20, seed=0, vocab=250):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def _run(eng, prompts, max_new=5):
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid, p, max_new_tokens=max_new))
    return {uid: r.tokens for uid, r in eng.run().items()}


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
