"""Backend conformance matrix + auto-selection + deprecation shim.

Load-bearing guarantees pinned here:

* every registered backend that ``supports()`` a call agrees with the
  ``reference`` oracle on a (mode x layout x causal x hdp-on/off) grid —
  token-for-token up to float-reduction-order tolerance (the backends
  compute identical math with different reduction schedules; see ATOL);
* off-TPU auto-selection resolves to the documented fallback chain for
  each call shape (pallas -> xla -> reference, pallas never auto
  off-TPU), and REPRO_ATTN_BACKEND forces the *default* spec only;
* the deprecated ``attn_backend=``/``cache_backend=`` string kwargs keep
  working end-to-end through Engine and launch/serve.py, emitting exactly
  ONE DeprecationWarning (these tests are the only exemption from the
  fast CI tier's ``-W error::DeprecationWarning``);
* ``Engine.summary()`` reports the resolved backend per phase.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import (AttnCall, AttnSpec, BackendUnsupported,
                             attention, get_backend, list_backends,
                             resolve_backend, spec_from_legacy)
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


@pytest.mark.parametrize("call,expect", [
    (AttnCall(mode="prefill", hdp=HDP, self_aligned=True), "xla_hdp"),
    (AttnCall(mode="prefill", self_aligned=True), "xla_dense"),
    (AttnCall(mode="prefill", trainable=True, hdp=HDP), "xla_hdp"),
    (AttnCall(mode="decode", hdp=HDP, per_slot=True), "xla_hdp"),
    (AttnCall(mode="decode", layout="paged", hdp=HDP, per_slot=True),
     "paged_hdp_decode"),
    (AttnCall(mode="decode", layout="paged", per_slot=True), "xla_dense"),
], ids=["prefill-hdp", "prefill-dense", "train-hdp", "decode-hdp",
        "paged-hdp", "paged-dense"])
def test_auto_resolution_off_tpu(call, expect, no_env):
    assert jax.default_backend() != "tpu"
    assert resolve_backend(call).name == expect


def test_explicit_pallas_and_fallback(no_env):
    paged = AttnCall(mode="decode", layout="paged",
                     hdp=HDP.replace(causal=True), per_slot=True)
    spec = AttnSpec(backend="pallas")
    # the "pallas" family tag prefers the gather-free page-table-native
    # kernel; the densifying block kernel stays explicitly addressable
    assert resolve_backend(paged, spec).name == "pallas_paged_decode"
    assert resolve_backend(
        paged, AttnSpec(backend="pallas_hdp_block")).name == "pallas_hdp_block"
    # non-causal paged calls can't use the gather-free kernel (its per-row
    # validity is an upper bound) but the block kernel still serves them
    noncausal = AttnCall(mode="decode", layout="paged", hdp=HDP,
                         causal=False, per_slot=True)
    assert resolve_backend(noncausal, spec).name == "pallas_hdp_block"
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


# -------------------------------------------------------- deprecation shim
def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, size=10).tolist() for _ in range(n)]


def _cfg(calib="none"):
    cfg = reduced(get_config("qwen2-1.5b"))
    return cfg.replace(hdp=cfg.hdp.replace(calib=calib))


@pytest.mark.filterwarnings("always::DeprecationWarning")
def test_engine_legacy_kwargs_single_warning():
    cfg = _cfg()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        eng = Engine(cfg, max_batch=1, max_len=64, prefill_buckets=(16,),
                     cache_backend="paged", attn_backend="pallas")
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1, [str(w.message) for w in dep]
    assert "AttnSpec" in str(dep[0].message)
    # the shim maps onto the same spec the new API would build
    assert eng.paged
    assert eng.attn_spec.backend == "pallas"
    eng.submit(Request(0, _prompts(1)[0], max_new_tokens=2))
    toks = eng.run()[0].tokens
    assert len(toks) == 2

    new = Engine(cfg, params=eng.params, max_batch=1, max_len=64,
                 prefill_buckets=(16,),
                 attn=AttnSpec(backend="pallas", layout="paged"))
    new.submit(Request(0, _prompts(1)[0], max_new_tokens=2))
    assert new.run()[0].tokens == toks


@pytest.mark.filterwarnings("always::DeprecationWarning")
def test_serve_legacy_flags_end_to_end():
    from repro.launch import serve
    args = serve.build_parser().parse_args(
        ["--arch", "qwen2-1.5b", "--requests", "1", "--max-new", "2",
         "--attn-backend", "xla", "--cache-backend", "dense"])
    with pytest.warns(DeprecationWarning):
        out = serve.run(args)
    assert out["completed"] == 1
    assert out["backend"] == "dense"
    assert out["attn_decode"] == "xla_hdp"


def test_engine_rejects_unknown_strings():
    with pytest.raises(ValueError):
        Engine(_cfg(), max_batch=1, max_len=32,
               attn="definitely-not-a-backend")
    with pytest.raises(ValueError):
        spec_from_legacy(attn_backend="cuda")
    with pytest.raises(ValueError):
        spec_from_legacy(cache_backend="ring")


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
