"""Backend conformance matrix + the one selection rule.

Load-bearing guarantees pinned here:

* every registered backend that ``supports()`` a call agrees with the
  ``reference`` oracle on a (mode x layout x causal x hdp-on/off) grid —
  token-for-token up to float-reduction-order tolerance (the backends
  compute identical math with different reduction schedules; see ATOL);
* auto-selection resolves every call class serving, training and the
  conformance grid make to the documented backend, off TPU and on it
  (pallas -> xla -> reference, pallas never auto off-TPU), and
  REPRO_ATTN_BACKEND forces the *default* spec only;
* ``Engine.summary()`` reports the backend whose ``run`` the engine's
  program dispatched, per phase.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import (AttnCall, AttnSpec, BackendUnsupported,
                             DraftProfile, attention, get_backend,
                             list_backends, resolve_backend)
from repro.attention import registry
from repro.configs import get_config
from repro.configs.base import reduced
from repro.core.config import HDPConfig
from repro.models.attention import scout_int8
from repro.serving import Engine, Request

F32 = jnp.float32

# float tolerance for backend-vs-oracle agreement: the implementations
# compute the same masked/blocked math with different reduction orders
# (scan-per-block vs full materialize vs online softmax), so bit equality
# is not guaranteed — agreement is pinned to this documented tolerance.
ATOL = 2e-5

B, N, G, HD = 1, 2, 2, 8
SQ = SK = 16
HDP = HDPConfig(block_q=4, block_k=4, rho_b=0.5, tau_h=0.0,
                normalize_head_score=True, calib="max")


def _qkv(seed, sq=SQ, sk=SK):
    rng = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (B, N, G, sq, HD), F32)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, sk, N, HD), F32)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, sk, N, HD), F32)
    return q, k, v


def _paged_setup(seed, hdp, n_pages=4):
    """One-slot paged cache: pools + table + positions (all pages visible)."""
    ps = hdp.block_k
    P = 1 + n_pages                       # page 0 = reserved scratch
    rng = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(rng, 0), (B, N, G, 1, HD), F32)
    ks = jax.random.normal(jax.random.fold_in(rng, 1), (P, N, ps, HD), F32)
    vs = jax.random.normal(jax.random.fold_in(rng, 2), (P, N, ps, HD), F32)
    cache = {"k_pages": ks, "v_pages": vs, "k_scout": scout_int8(ks, hdp)}
    table = jnp.arange(1, P, dtype=jnp.int32).reshape(B, n_pages)
    sk = n_pages * ps
    pos = jnp.full((B, 1), sk - 1, jnp.int32)
    q_pos = pos[:, None, None, :]
    ar = jnp.arange(sk)
    k_pos = jnp.where(ar[None] <= pos, ar, -1)[:, None, None, :]
    return q, cache, table, q_pos, k_pos


def _run(call, backend_name, seed=0):
    spec = AttnSpec(backend=backend_name, allow_fallback=False)
    if call.layout == "paged":
        hdp = call.hdp if call.hdp is not None else HDP
        q, cache, table, q_pos, k_pos = _paged_setup(seed, hdp)
        out, _ = attention(q, None, None, call, spec=spec, q_pos=q_pos,
                           k_pos=k_pos, cache=cache, page_table=table)
        return out
    if call.mode == "decode":
        q, k, v = _qkv(seed, sq=1)
        q_pos, k_pos = jnp.asarray([SK - 1]), jnp.arange(SK)
    else:
        q, k, v = _qkv(seed)
        q_pos = k_pos = jnp.arange(SQ)
    out, _ = attention(q, k, v, call, spec=spec, q_pos=q_pos, k_pos=k_pos)
    return out


def _grid():
    cells = []
    for mode in ("prefill", "decode"):
        for causal in (True, False):
            for hdp_on in (True, False):
                hdp = HDP.replace(causal=causal) if hdp_on else None
                cells.append(AttnCall(
                    mode=mode, layout="dense", causal=causal, hdp=hdp,
                    self_aligned=(mode == "prefill")))
    for hdp_on in (True, False):
        cells.append(AttnCall(
            mode="decode", layout="paged", causal=True,
            hdp=HDP.replace(causal=True, calib="none") if hdp_on else None))
    return cells


def _cell_id(call):
    return (f"{call.mode}-{call.layout}-"
            f"{'causal' if call.causal else 'full'}-"
            f"{'hdp' if call.hdp is not None else 'dense'}")


GRID = _grid()


@pytest.mark.parametrize("call", GRID, ids=_cell_id)
def test_backends_agree_with_reference(call):
    ref = _run(call, "reference")
    ran = []
    for b in list_backends():
        if b.name == "reference" or not b.supports(call):
            continue
        out = _run(call, b.name)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=ATOL, rtol=ATOL,
            err_msg=f"{b.name} disagrees with reference on {_cell_id(call)}")
        ran.append(b.name)
    assert ran, f"no production backend supports {_cell_id(call)}"


def test_every_backend_covered_by_grid():
    """Each of the seven registered backends runs in >= 1 conformance cell."""
    names = {b.name for b in list_backends()}
    assert names == {"reference", "xla_dense", "xla_hdp", "paged_hdp_decode",
                     "pallas_flash", "pallas_hdp_block",
                     "pallas_paged_decode"}
    covered = {"reference"}
    for call in GRID:
        covered |= {b.name for b in list_backends() if b.supports(call)}
    assert covered == names


def test_reference_matches_core_oracle():
    """The model-layout oracle agrees with core.hdp's Algorithm 2
    transliteration on an aligned causal self-attention cell."""
    from repro.core.hdp import hdp_attention_reference
    hdp = HDP.replace(causal=True)
    q, k, v = _qkv(7)
    call = AttnCall(mode="prefill", layout="dense", causal=True, hdp=hdp,
                    self_aligned=True)
    out = _run(call, "reference", seed=7)
    # core layout: [B,H,S,hd] with k/v repeated across the GQA group
    qh = q.reshape(B, N * G, SQ, HD)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    core, _ = hdp_attention_reference(qh, kh, vh, hdp)
    np.testing.assert_allclose(np.asarray(out.reshape(B, N * G, SQ, HD)),
                               np.asarray(core), atol=ATOL, rtol=ATOL)


# -------------------------------------------------------------- resolution
@pytest.fixture
def no_env(monkeypatch):
    monkeypatch.delenv("REPRO_ATTN_BACKEND", raising=False)


# the HDP config build_attn_call hands a causal call (hdp.causal follows
# the call's own mask)
CAUSAL = HDP.replace(causal=True)
PAGED = AttnCall(mode="decode", layout="paged", hdp=CAUSAL, per_slot=True)

#: (id, call, backend off TPU, backend on TPU): one row per call class
#: that serving, training or the conformance grid makes
SELECTION = [
    ("prefill-aligned-hdp",
     AttnCall(mode="prefill", hdp=CAUSAL, self_aligned=True),
     "xla_hdp", "pallas_hdp_block"),
    ("prefill-aligned-dense",
     AttnCall(mode="prefill", self_aligned=True),
     "xla_dense", "pallas_flash"),
    ("prefill-aligned-hdp-noncausal",
     AttnCall(mode="prefill", causal=False, hdp=HDP, self_aligned=True),
     "xla_hdp", "pallas_hdp_block"),
    ("prefill-aligned-dense-noncausal",
     AttnCall(mode="prefill", causal=False, self_aligned=True),
     "xla_dense", "pallas_flash"),
    # the engine prefills into a request cache: not self-aligned
    ("prefill-request-cache-hdp",
     AttnCall(mode="prefill", hdp=CAUSAL),
     "xla_hdp", "xla_hdp"),
    ("prefill-request-cache-dense",
     AttnCall(mode="prefill"),
     "xla_dense", "xla_dense"),
    ("prefill-windowed-hdp",
     AttnCall(mode="prefill", window=8, hdp=CAUSAL, self_aligned=True),
     "xla_hdp", "xla_hdp"),
    ("prefill-windowed-dense",
     AttnCall(mode="prefill", window=8, self_aligned=True),
     "xla_dense", "xla_dense"),
    ("train-hdp",
     AttnCall(mode="prefill", hdp=CAUSAL, self_aligned=True,
              trainable=True),
     "xla_hdp", "xla_hdp"),
    ("train-dense",
     AttnCall(mode="prefill", self_aligned=True, trainable=True),
     "xla_dense", "xla_dense"),
    ("decode-dense-hdp",
     AttnCall(mode="decode", hdp=CAUSAL, per_slot=True),
     "xla_hdp", "xla_hdp"),
    ("decode-dense-nohdp",
     AttnCall(mode="decode", per_slot=True),
     "xla_dense", "xla_dense"),
    ("decode-dense-verify",
     AttnCall(mode="decode", hdp=CAUSAL, per_slot=True, verify=True),
     "xla_hdp", "xla_hdp"),
    ("decode-dense-draft",
     AttnCall(mode="decode", hdp=CAUSAL, per_slot=True,
              draft=DraftProfile()),
     "xla_hdp", "xla_hdp"),
    ("decode-cross-hdp",
     AttnCall(mode="decode", causal=False, hdp=HDP),
     "xla_hdp", "xla_hdp"),
    # the cells' decode: a causal paged call over a static-grid pool
    ("paged-grid", PAGED,
     "paged_hdp_decode", "pallas_paged_decode"),
    ("paged-absmax", PAGED.replace(kv_scale="absmax"),
     "paged_hdp_decode", "paged_hdp_decode"),
    ("paged-windowed", PAGED.replace(window=8),
     "paged_hdp_decode", "paged_hdp_decode"),
    ("paged-verify", PAGED.replace(verify=True),
     "paged_hdp_decode", "pallas_paged_decode"),
    ("paged-verify-absmax", PAGED.replace(verify=True, kv_scale="absmax"),
     "paged_hdp_decode", "paged_hdp_decode"),
    ("paged-draft", PAGED.replace(draft=DraftProfile()),
     "paged_hdp_decode", "paged_hdp_decode"),
    ("paged-noncausal", PAGED.replace(causal=False, hdp=HDP),
     "paged_hdp_decode", "paged_hdp_decode"),
    ("paged-approx-softmax",
     PAGED.replace(hdp=CAUSAL.replace(approx_softmax=True)),
     "paged_hdp_decode", "paged_hdp_decode"),
    ("paged-nohdp", PAGED.replace(hdp=None),
     "xla_dense", "xla_dense"),
]


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("call,off_tpu,on_tpu",
                         [row[1:] for row in SELECTION],
                         ids=[row[0] for row in SELECTION])
def test_selection_rule(call, off_tpu, on_tpu, platform, no_env,
                        monkeypatch):
    """The registry's fixed rule: the highest-ranked backend whose
    ``supports(call)`` holds, Pallas ranked first only on TPU."""
    monkeypatch.setattr(registry, "_on_tpu", lambda: platform == "tpu")
    expect = on_tpu if platform == "tpu" else off_tpu
    assert resolve_backend(call).name == expect


def test_explicit_pallas_and_fallback(no_env):
    paged = AttnCall(mode="decode", layout="paged",
                     hdp=HDP.replace(causal=True), per_slot=True)
    spec = AttnSpec(backend="pallas")
    # the "pallas" family tag picks the gather-free page-table-native
    # kernel; the block kernel serves no paged call, so asking for it
    # falls down the chain (or raises without fallback)
    assert resolve_backend(paged, spec).name == "pallas_paged_decode"
    block = AttnSpec(backend="pallas_hdp_block")
    assert resolve_backend(paged, block).name == "paged_hdp_decode"
    with pytest.raises(BackendUnsupported):
        resolve_backend(paged, block.replace(allow_fallback=False))
    # non-causal paged calls can't use the gather-free kernel (its per-row
    # validity is an upper bound): no Pallas backend serves them
    noncausal = AttnCall(mode="decode", layout="paged", hdp=HDP,
                         causal=False, per_slot=True)
    assert resolve_backend(noncausal, spec).name == "paged_hdp_decode"
    # the FUM kernels cannot express a sliding window's lower bound ->
    # windowed calls fall down the chain to the XLA implementation
    windowed = paged.replace(window=8)
    assert resolve_backend(windowed, spec).name == "paged_hdp_decode"
    with pytest.raises(BackendUnsupported):
        resolve_backend(windowed, spec.replace(allow_fallback=False))
    with pytest.raises(KeyError):
        resolve_backend(paged, AttnSpec(backend="not-a-backend"))


def test_env_var_forces_every_auto_resolution(monkeypatch):
    monkeypatch.setenv("REPRO_ATTN_BACKEND", "reference")
    call = AttnCall(mode="prefill", hdp=HDP, self_aligned=True)
    assert resolve_backend(call).name == "reference"
    # "auto" consults the env even through an explicit spec (a spec that
    # only pins the layout must not dodge the CI reference leg) ...
    assert resolve_backend(call, AttnSpec(layout="dense")).name == "reference"
    # ... but explicit non-auto requests win over the env override
    assert resolve_backend(call, AttnSpec(backend="xla")).name == "xla_hdp"


def test_engine_validates_per_mode_overrides():
    with pytest.raises(ValueError, match="decode"):
        Engine(_cfg(), max_batch=1, max_len=32,
               attn=AttnSpec(decode="palas"))


def test_supports_capability_edges():
    trainable = AttnCall(mode="prefill", hdp=HDP, self_aligned=True,
                         trainable=True)
    assert not get_backend("pallas_hdp_block").supports(trainable)
    assert not get_backend("pallas_flash").supports(
        AttnCall(mode="prefill", self_aligned=True, trainable=True))
    # disabled HDP configs normalize to hdp=None at construction
    off = AttnCall(mode="prefill", hdp=HDP.replace(enabled=False))
    assert off.hdp is None
    with pytest.raises(ValueError):
        AttnCall(mode="prefill", layout="paged")


# ------------------------------------------------------------ engine reports
def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, size=10).tolist() for _ in range(n)]


def _cfg(calib="none"):
    cfg = reduced(get_config("qwen2-1.5b"))
    return cfg.replace(hdp=cfg.hdp.replace(calib=calib))


def test_engine_rejects_unknown_strings():
    with pytest.raises(ValueError):
        Engine(_cfg(), max_batch=1, max_len=32,
               attn="definitely-not-a-backend")
    with pytest.raises(ValueError):
        Engine(_cfg(), max_batch=1, max_len=32, attn=AttnSpec(backend="cuda"))
    with pytest.raises(ValueError):
        AttnSpec(layout="ring")


def test_engine_summary_reports_resolved_backends(no_env):
    eng = Engine(_cfg(), max_batch=1, max_len=32, prefill_buckets=(16,))
    s = eng.summary()
    assert s["attn_backend_prefill"] == "xla_hdp"
    assert s["attn_backend_decode"] == "paged_hdp_decode"
    dense = Engine(_cfg(), params=eng.params, max_batch=1, max_len=32,
                   attn=AttnSpec(backend="reference", layout="dense"))
    s = dense.summary()
    assert s["attn_backend_prefill"] == "reference"
    assert s["attn_backend_decode"] == "reference"


@pytest.mark.parametrize("phase", ["decode", "verify"])
@pytest.mark.parametrize("kv_scale", ["grid", "absmax"])
def test_reported_backend_runs(kv_scale, phase, monkeypatch):
    """The backend ``summary()`` names for a phase is the one whose
    ``run`` the engine's program dispatched, and the Pallas paged kernel
    walks pages exactly when that backend is ``pallas_paged_decode``
    (an absmax pool is refused by its predicate, not rerouted inside
    it). The ``pallas`` tag asks for the kernel wherever it can serve."""
    ran = []

    def spy(b):
        def run(q, k, v, call, **kw):
            if call.mode == "decode" and call.draft is None:
                ran.append((call.verify, b.name))
            return b.run(q, k, v, call, **kw)
        return dataclasses.replace(b, run=run)

    for b in list_backends():
        monkeypatch.setitem(registry._REGISTRY, b.name, spy(b))
    verify = phase == "verify"
    eng = Engine(_cfg(), max_batch=1, max_len=32, prefill_buckets=(16,),
                 collect_stats=True, spec_decode=verify, draft_len=2,
                 attn=AttnSpec(backend="pallas", layout="paged",
                               kv_dtype="int8", kv_scale=kv_scale))
    eng.submit(Request(0, _prompts(1)[0], max_new_tokens=3))
    eng.run()
    s = eng.summary()
    reported = (eng.resolved_backend("verify") if verify
                else s["attn_backend_decode"])
    dispatched = {name for v, name in ran if v == verify}
    assert dispatched == {reported}
    assert reported == ("pallas_paged_decode" if kv_scale == "grid"
                        else "paged_hdp_decode")
    assert (s["kernel_block_pages"] > 0) == (reported
                                             == "pallas_paged_decode")
