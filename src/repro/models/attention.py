"""Unified multi-head attention with HDP as a first-class feature.

Paths (selected by mode/config, all GQA-grouped, fp32 accumulation):

* ``chunked``   — flash-style lax.scan over KV chunks (train / prefill);
                  memory O(Sq * chunk) instead of O(Sq * Sk).
* ``local``     — block-local sliding-window attention, cost O(S * w).
* ``decode``    — single-query attention over a KV cache.
* ``hdp_*``     — the paper's pipeline, blockwise: integer scout pass ->
                  row-balanced block mask + early head gate -> approximate
                  (QK - FQ FK) attention on surviving blocks. Prefill scans
                  q-blocks twice (scout, attend); decode prunes KV pages.

Tensor conventions: activations x [B, S, D]; q [B, N, G, Sq, hd] where
N = kv heads, G = query group size (N*G = n_heads); k/v [B, Sk, N, hd].
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.attention import AttnCall, AttnSpec, attention
from repro.core import blocking
from repro.core.config import HDPConfig
from repro.core.hdp import calibrated_split, decode_scout, scout_decision
from repro.core.quant import (FRAC_SCOUT_SCALE, POISON_CODE, encode_pool,
                              encode_pool_scaled, pool_int_bits, pool_scale,
                              pool_view_finite, quantize_and_split,
                              roundtrip_pool, scout_frac_codes,
                              scout_int_codes)
from repro.distribution.sharding import shard_activation as shd
from repro.models import layers as L

_NEG = -1e30
F32 = jnp.float32


# ------------------------------------------------------------------ params
def attn_init(cfg, rng, dtype) -> Tuple[Dict, Dict]:
    d, h, n, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p = {
        "wq": L.dense_init(L.key_for(rng, "wq"), (d, h, hd), dtype),
        "wk": L.dense_init(L.key_for(rng, "wk"), (d, n, hd), dtype),
        "wv": L.dense_init(L.key_for(rng, "wv"), (d, n, hd), dtype),
        "wo": L.dense_init(L.key_for(rng, "wo"), (h, hd, d), dtype, in_axis=-3),
    }
    s = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        p.update(bq=jnp.zeros((h, hd), dtype), bk=jnp.zeros((n, hd), dtype),
                 bv=jnp.zeros((n, hd), dtype))
        s.update(bq=("heads", "head_dim"), bk=("kv_heads", "head_dim"),
                 bv=("kv_heads", "head_dim"))
    if cfg.qk_norm:
        p.update(q_norm=jnp.ones((hd,), dtype), k_norm=jnp.ones((hd,), dtype))
        s.update(q_norm=("head_dim",), k_norm=("head_dim",))
    return p, s


# -------------------------------------------------------------- core maths
def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_axis(x, axis, target):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, pad)
    return jnp.pad(x, w)


def _mask_bias(q_pos, k_pos, causal: bool, window: int):
    """[..., Sq, Sk] additive bias from position validity."""
    # include q validity so the mask always carries the full [Sq, Sk]
    # extent (cross-attention has neither causal nor window terms).
    valid = (k_pos[..., None, :] >= 0) & (q_pos[..., :, None] >= 0)
    if causal:
        valid &= q_pos[..., :, None] >= k_pos[..., None, :]
    if window:
        valid &= (q_pos[..., :, None] - k_pos[..., None, :]) < window
    return valid


def chunked_attention(q, k, v, *, q_pos, k_pos, chunk: int,
                      causal: bool = True, window: int = 0):
    """Flash-style scan over KV chunks. q [B,N,G,Sq,hd]; k,v [B,Sk,N,hd]."""
    B, N, G, Sq, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / (hd ** 0.5)
    nc = max(1, -(-Sk // chunk))
    Skp = nc * chunk
    k = _pad_axis(k, 1, Skp)
    v = _pad_axis(v, 1, Skp)
    k_pos = _pad_axis(k_pos + 1, 0, Skp) - 1  # pads become -1 (invalid)

    kc = k.reshape(B, nc, chunk, N, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, chunk, N, hd).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(nc, chunk)

    m0 = jnp.full((B, N, G, Sq), _NEG, F32)
    l0 = jnp.zeros((B, N, G, Sq), F32)
    a0 = jnp.zeros((B, N, G, Sq, hd), F32)

    def body(carry, xs):
        m, l, acc = carry
        ki, vi, pi = xs
        s = jnp.einsum("bngqh,bcnh->bngqc", q, ki,
                       preferred_element_type=F32) * scale
        valid = _mask_bias(q_pos, pi, causal, window)
        s = jnp.where(valid, s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        pv = jnp.einsum("bngqc,bcnh->bngqh", p.astype(v.dtype), vi,
                        preferred_element_type=F32)
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def local_attention(q, k, v, *, q_pos, k_pos, window: int, causal: bool = True):
    """Block-local sliding window: each q block attends self+prev block.

    Requires block size == window; cost O(S * 2w * hd)."""
    B, N, G, Sq, hd = q.shape
    Sk = k.shape[1]
    c = window
    Sqp, Skp = _ceil_to(Sq, c), _ceil_to(Sk, c)
    assert Sqp == Skp, "local attention expects aligned q/k (self-attn)"
    qb = _pad_axis(q, 3, Sqp).reshape(B, N, G, Sqp // c, c, hd)
    kb = _pad_axis(k, 1, Skp).reshape(B, Skp // c, c, N, hd)
    vb = _pad_axis(v, 1, Skp).reshape(B, Skp // c, c, N, hd)
    qp = _pad_axis(q_pos + 1, 0, Sqp).reshape(Sqp // c, c) - 1
    kp = _pad_axis(k_pos + 1, 0, Skp).reshape(Skp // c, c) - 1

    def pair(x):  # concat previous block: [B, nb, 2c, N, hd]
        prev = jnp.roll(x, 1, axis=1).at[:, 0].set(0.0)
        return jnp.concatenate([prev, x], axis=2)

    k2, v2 = pair(kb), pair(vb)
    kp2 = jnp.concatenate([jnp.roll(kp, 1, 0).at[0].set(-1), kp], axis=1)
    scale = 1.0 / (hd ** 0.5)
    s = jnp.einsum("bngtqh,btcnh->bngtqc", qb, k2,
                   preferred_element_type=F32) * scale
    valid = _mask_bias(qp, kp2, causal, window)  # [nb, c, 2c]
    s = jnp.where(valid, s, _NEG)
    mx = s.max(-1, keepdims=True)
    p = jnp.exp(s - mx)
    p = jnp.where(valid, p, 0.0)
    den = jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("bngtqc,btcnh->bngtqh", (p / den).astype(v.dtype), v2,
                     preferred_element_type=F32)
    out = out.reshape(B, N, G, Sqp, hd)[:, :, :, :Sq]
    return out.astype(q.dtype)


def decode_attention(q, k, v, *, q_pos, k_pos, window: int = 0,
                     causal: bool = True):
    """Single (or few) query tokens vs cache. q [B,N,G,Sq,hd], k/v [B,Sk,N,hd]."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bngqh,bsnh->bngqs", q, k, preferred_element_type=F32) * scale
    valid = _mask_bias(q_pos, k_pos, causal, window)
    s = jnp.where(valid, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid, p, 0.0)
    out = jnp.einsum("bngqs,bsnh->bngqh", p.astype(v.dtype), v,
                     preferred_element_type=F32)
    return out.astype(q.dtype)


# ----------------------------------------------------------------- HDP path
def hdp_prefill_attention(q, k, v, *, q_pos, k_pos, hdp: HDPConfig,
                          window: int = 0, return_stats: bool = False):
    """Two-pass blockwise HDP (Alg. 2 adapted to TPU-sized tiles).

    Pass A: integer scout per q-block -> theta, row threshold, keep mask,
    head importance. Pass B: approximate attention on surviving blocks.
    """
    B, N, G, Sq, hd = q.shape
    Sk = k.shape[1]
    bq, bk = hdp.block_q, hdp.block_k
    Sqp, Skp = _ceil_to(Sq, bq), _ceil_to(Sk, bk)
    nq, nk = Sqp // bq, Skp // bk
    scale = 1.0 / (hd ** 0.5)

    sq, qq, iq, fq = calibrated_split(_pad_axis(q, 3, Sqp).astype(F32), hdp)
    sk, kq, ik, fk = calibrated_split(_pad_axis(k, 1, Skp).astype(F32), hdp)
    score_rescale = 1.0 / (sq * sk)
    vp = _pad_axis(v, 1, Skp)
    qp = _pad_axis(q_pos + 1, 0, Sqp) - 1
    kp = _pad_axis(k_pos + 1, 0, Skp) - 1

    def per_qblock(x):  # [B,N,G,Sqp,...] -> [nq, B,N,G,bq,...]
        xs = x.reshape(B, N, G, nq, bq, *x.shape[4:])
        return jnp.moveaxis(xs, 3, 0)

    iq_b, qq_b, fq_b = per_qblock(iq), per_qblock(qq), per_qblock(fq)
    qp_b = qp.reshape(nq, bq)

    # ---- Pass A: integer scout -> keep mask, head importance ----
    with jax.named_scope("hdp.scout"):
        def scout(carry, xs):
            th_acc, n_acc, nb_acc = carry
            iq_i, qp_i = xs
            s_int = jnp.einsum("bngqh,bsnh->bngqs", iq_i, ik,
                               preferred_element_type=F32)
            valid = _mask_bias(qp_i, kp, hdp.causal, window)
            theta, bvalid = blocking.pooled_block_theta(s_int, valid, bk)
            if hdp.block_pruning:
                thr = blocking.row_threshold(theta, hdp.rho_b, bvalid)
                keep = blocking.block_keep_mask(theta, thr, bvalid)
            else:
                keep = jnp.broadcast_to(bvalid, theta.shape)
            th_acc = th_acc + jnp.where(bvalid, theta, 0.0).sum(-1)
            n_acc = n_acc + valid.sum().astype(F32)
            nb_acc = nb_acc + bvalid.sum().astype(F32)
            return (th_acc, n_acc, nb_acc), keep

        (theta_head, n_valid, n_blocks), keep_rows = jax.lax.scan(
            scout, (jnp.zeros((B, N, G), F32), jnp.zeros((), F32),
                    jnp.zeros((), F32)), (iq_b, qp_b))
        if hdp.normalize_head_score:
            theta_head = theta_head / jnp.maximum(n_valid, 1.0)
        head_kept = (theta_head > hdp.tau_h) if hdp.head_pruning \
            else jnp.ones_like(theta_head, bool)

    # ---- Pass B: approximate attention on surviving blocks ----
    with jax.named_scope("hdp.attend"):
        def attend(_, xs):
            qq_i, fq_i, qp_i, keep_i = xs
            s = jnp.einsum("bngqh,bsnh->bngqs", qq_i, kq,
                           preferred_element_type=F32)
            if hdp.approx:
                s = s - jnp.einsum("bngqh,bsnh->bngqs", fq_i, fk,
                                   preferred_element_type=F32)
            s = s * (scale * score_rescale)
            valid = _mask_bias(qp_i, kp, hdp.causal, window)
            keep_e = jnp.repeat(keep_i, bk, axis=-1)[..., None, :] & valid
            s = jnp.where(keep_e, s, _NEG)
            softmax = blocking.approx_softmax if hdp.approx_softmax else None
            if softmax is not None:
                p = softmax(s, keep_e)
            else:
                mx = s.max(-1, keepdims=True)
                p = jnp.exp(s - mx)
                p = jnp.where(keep_e, p, 0.0)
                p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
            o = jnp.einsum("bngqs,bsnh->bngqh", p.astype(vp.dtype), vp,
                           preferred_element_type=F32)
            return (), o

        _, outs = jax.lax.scan(attend, (), (qq_b, fq_b, qp_b, keep_rows))
        out = jnp.moveaxis(outs, 0, 3).reshape(B, N, G, Sqp, hd)[:, :, :, :Sq]
    out = out * head_kept[..., None, None].astype(out.dtype)

    stats = None
    if return_stats:
        kept = keep_rows.astype(F32).sum() / (B * N * G)
        stats = {
            "block_sparsity": 1.0 - kept / jnp.maximum(n_blocks, 1.0),
            "head_sparsity": 1.0 - head_kept.astype(F32).mean(),
            "theta_head": theta_head,
        }
    return out.astype(q.dtype), stats


def _expand_keep(keep, block_k, valid, ndim):
    """[..., nk] or [..., Sq, nk] block keep -> element mask of `ndim` dims.

    Pooled (decode) masks lack the query axis and broadcast over it;
    per-query (verify) masks already carry Sq and expand in place."""
    keep_e = jnp.repeat(keep, block_k, axis=-1)
    if keep_e.ndim < ndim:
        keep_e = keep_e[..., None, :]
    return keep_e & valid


def _head_gate(out, head_kept):
    """Early head gate: pooled [...] or per-query [..., Sq] gates both
    broadcast against [..., Sq, hd] by appending trailing axes."""
    gate = head_kept
    while gate.ndim < out.ndim:
        gate = gate[..., None]
    return out * gate.astype(out.dtype)


def _approx_block_attention(qq, fq, kq, fk, v, keep, valid, head_kept, *,
                            block_k, scale, approx, scores=None):
    """Shared decode stage: approximate scores (QK^T - FQ FK^T) on blocks
    surviving `keep`, exclusion softmax, early head gate.

    `scale` folds 1/sqrt(hd) and any calibration rescale; `block_k` is the
    width the [..., nk] keep mask expands by to match the score columns.
    `scores` (pre-scale) overrides the QK^T - FQ FK^T computation — the
    self-speculative draft hands its integer/scout scores in here."""
    if scores is None:
        s = jnp.einsum("bngqh,bsnh->bngqs", qq, kq,
                       preferred_element_type=F32)
        if approx:
            s = s - jnp.einsum("bngqh,bsnh->bngqs", fq, fk,
                               preferred_element_type=F32)
    else:
        s = scores
    s = s * scale
    keep_e = _expand_keep(keep, block_k, valid, s.ndim)
    s = jnp.where(keep_e, s, _NEG)
    mx = s.max(-1, keepdims=True)
    p = jnp.exp(s - mx)
    p = jnp.where(keep_e, p, 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("bngqs,bsnh->bngqh", p.astype(v.dtype), v,
                     preferred_element_type=F32)
    return _head_gate(out, head_kept)


def _block_sparsity_stats(keep, bvalid, head_kept):
    """Per-slot pruned fractions over *valid* blocks — decode-mode stats
    leaves carry the batch dim ([B]) so the serving engine can mask
    parked slots out of the batchwise means (prefill stats stay scalar:
    exact-size stacking means every row is real)."""
    ax = tuple(range(1, keep.ndim))
    kept = (keep & bvalid).astype(F32).sum(ax)
    tot = jnp.maximum(
        jnp.broadcast_to(bvalid, keep.shape).astype(F32).sum(ax), 1.0)
    hax = tuple(range(1, head_kept.ndim))
    return {"block_sparsity": 1.0 - kept / tot,
            "head_sparsity": 1.0 - head_kept.astype(F32).mean(hax)}


def hdp_decode_attention(q, k, v, *, q_pos, k_pos, hdp: HDPConfig,
                         window: int = 0, return_stats: bool = False,
                         draft=None, per_query: bool = False):
    """KV-page pruning for decode (TPU adaptation, DESIGN.md §2).

    The integer scout reads K (int8-representable) once; pruned pages'
    V (and full-precision K) never need fetching — the memory-roofline win.

    ``draft`` (a DraftProfile, thresholds already overlaid into ``hdp``)
    switches the score source to the draft approximation; ``per_query``
    runs the scout per query row (the multi-query verify shape).
    """
    B, N, G, Sq, hd = q.shape
    Sk = k.shape[1]
    bk = hdp.block_k
    Skp = _ceil_to(Sk, bk)
    scale = 1.0 / (hd ** 0.5)

    sq, qq, iq, fq = calibrated_split(q.astype(F32), hdp)
    sk, kq, ik, fk = calibrated_split(_pad_axis(k, 1, Skp).astype(F32), hdp)
    score_rescale = 1.0 / (sq * sk)
    vp = _pad_axis(v, 1, Skp)
    kp = _pad_axis(k_pos + 1, -1 if k_pos.ndim > 1 else 0, Skp) - 1

    s_int = jnp.einsum("bngqh,bsnh->bngqs", iq, ik, preferred_element_type=F32)
    valid = _mask_bias(q_pos, kp, hdp.causal, window)
    # the (small) query group is pooled into one block row per head —
    # unless per_query, where each verify row scouts for itself
    keep, bvalid, theta, theta_head, head_kept = decode_scout(
        s_int, valid, hdp, per_query=per_query)

    scores = None
    if draft is not None and draft.scores != "approx":
        # draft scores from the scout copies: s_int alone ("int") or
        # QQ·IK + IQ·FK^ ("scout"). The dense layout recomputes the
        # copies per step (its cache holds full-precision K), but the
        # *score* semantics — including FK's 2^-6 re-quantization — match
        # the paged scout-pool draft bit for bit.
        scores = s_int
        if draft.scores == "scout":
            fkh = jnp.round(fk * FRAC_SCOUT_SCALE) / FRAC_SCOUT_SCALE
            scores = scores \
                + jnp.einsum("bngqh,bsnh->bngqs", fq, ik,
                             preferred_element_type=F32) \
                + jnp.einsum("bngqh,bsnh->bngqs", iq, fkh,
                             preferred_element_type=F32)

    out = _approx_block_attention(qq, fq, kq, fk, vp, keep, valid, head_kept,
                                  block_k=bk, scale=scale * score_rescale,
                                  approx=hdp.approx, scores=scores)

    stats = None
    if return_stats:
        stats = {**_block_sparsity_stats(keep, bvalid, head_kept),
                 "theta_head": theta_head}
    return out.astype(q.dtype), stats


def _fixed_split(x, hdp: HDPConfig):
    """Calibration-free fixed-point split (xq, I, F).

    The paged serving cache stores the scout copy of K at *write* time, so
    the grid must be static (the paper's co-processor model: the host hands
    over pre-quantized fixed-point tensors). Elementwise by construction —
    values in pruned pages can never leak into kept positions through a
    data-dependent scale.
    """
    return quantize_and_split(x.astype(F32), hdp.int_bits, hdp.frac_bits)


def scout_int8(k, hdp: HDPConfig):
    """Write-time int8 scout copy of K (what FUM always streams).

    Thin config-aware wrapper over the shared ``core.quant`` pool-quant
    module — the SAME codes a quantized pool derives as its stage-1
    view, so fp32 and int8 pools scout on identical grids."""
    return scout_int_codes(k, hdp.int_bits, hdp.frac_bits)


def scout_frac_int8(k, hdp: HDPConfig):
    """Write-time int8 quantized-fraction scout copy of K.

    The self-speculative draft reconstructs near-exact approximate scores
    from the two int8 copies alone (``QQ·IK + IQ·FK^``), so a draft step
    never reads the full-precision K pool; stored only when a fp32-pool
    engine speculates (quantized pools derive the fraction view from
    their codes instead)."""
    return scout_frac_codes(k, hdp.int_bits, hdp.frac_bits)


def gather_pages(pool, idx, layer=None):
    """Pages ``idx`` of a head-major [P, N, ps, hd] pool, token-major:
    [..., ps, N, hd]. The pool is stored head-major so one head's page is
    a whole [ps, hd] tile the Pallas kernel DMAs as stored; the XLA paths
    read the same pages through this view. With ``layer`` the pool is the
    layer-stacked [L, P, N, ps, hd] one, read in a single gather (no
    per-layer slice is materialized)."""
    g = pool[idx] if layer is None else pool[layer, idx]
    return jnp.swapaxes(g, -3, -2)


def write_pages(pool, pidx, off, rows, layer=None):
    """Write token rows [B, S, N, hd] into a head-major pool: row (b, s)
    lands on page ``pidx[b, s]`` at offset ``off[b, s]``, every kv head.

    One dynamic-update-slice per row: it updates the pool in place in its
    own tiled layout, where a scatter makes XLA relayout the whole pool
    on TPU (twice: to the scatter's tiling and back). ``layer`` selects
    the layer of a layer-stacked [L, P, N, ps, hd] pool. Rows redirected
    to the same scratch position overwrite each other in row order."""
    B, S = pidx.shape
    rows = rows.astype(pool.dtype)
    for b in range(B):
        for s in range(S):
            row = rows[b, s][None, :, None, :]          # [1, N, 1, hd]
            start = (pidx[b, s], 0, off[b, s], 0)
            if layer is not None:
                row, start = row[None], (layer,) + start
            pool = jax.lax.dynamic_update_slice(pool, row, start)
    return pool


def layer_view(cache):
    """Per-layer view of a paged cache: a layer-stacked cache (one that
    carries its ``"layer"`` index) sliced to that layer, any other
    returned as is."""
    if cache is None or "layer" not in cache:
        return cache
    li = cache["layer"]
    return {k: v[li] for k, v in cache.items() if k != "layer"}


def _dequant_pages(pages, scale):
    """Gathered pool pages [..., ps, N, hd] + per-page scales [..., N]
    -> fp32 values; the POISON_CODE sentinel (int8 pools) and a NaN page
    scale both surface as NaN (the stage-3 poison tripwires)."""
    if pages.dtype == jnp.int8:
        vals = jnp.where(pages == POISON_CODE, jnp.nan, pages.astype(F32))
    else:  # fp8 V: the exponent does the scale's job (scale stays 1.0)
        vals = pages.astype(F32)
    return vals * scale[..., None, :, None].astype(F32)


def resolve_write_pages(positions, page_table, page_size, write_floor=None):
    """[B, S] write positions -> [B, S] destination pool page per write.

    THE single implementation of the write-side position->page
    resolution and its safety fences — the decode K/V scatter and the
    speculative rollback poison must agree on it exactly:

    * columns past the table width redirect to the scratch page
      (speculative staging can run past the allocation near max_len);
    * columns below the slot's ``write_floor`` redirect to the scratch
      page (shared read-only prefix pages are immutable);
    * unallocated columns are already 0 (scratch) in the table.
    """
    nP = page_table.shape[1]
    pcol = positions // page_size
    pidx = jnp.take_along_axis(page_table, jnp.minimum(pcol, nP - 1), axis=1)
    pidx = jnp.where(pcol < nP, pidx, 0)
    if write_floor is not None:
        pidx = jnp.where(pcol >= write_floor[:, None], pidx, 0)
    return pidx


def _paged_scan_attention(qq, fq, k_pool, v_pool, gather_idx, keep, valid,
                          head_kept, *, hdp: HDPConfig, ps: int, cpp: int,
                          scale: float, k_scale=None, v_scale=None,
                          layer=None):
    """Stage 2+3 as an online-softmax scan over page chunks.

    Peak stage-2 memory is O(B * cpp * ps) — one chunk of gathered pages —
    instead of the O(B * Sk) dense materialization; pruned pages stay
    scratch-redirected, so their full-precision memory is never read.
    Quantized pools dequantize per chunk (``k_scale``/``v_scale`` are the
    per-page scale arrays), so dequantized tiles never round-trip HBM.
    Reduction order differs from the one-shot dense softmax by page-chunk
    grouping (ULP-level output differences across the chunk boundary).
    """
    B, N, G, Sq, hd = qq.shape
    nP = gather_idx.shape[1]
    nc = -(-nP // cpp)
    pad = nc * cpp - nP
    Sk = nP * ps
    idx_p = jnp.pad(gather_idx, ((0, 0), (0, pad)))       # pads -> scratch
    keep_p = jnp.pad(keep, ((0, 0),) * (keep.ndim - 1) + ((0, pad),))
    valid_f = jnp.broadcast_to(valid, (B, 1, 1, Sq, Sk))
    valid_p = jnp.pad(valid_f, ((0, 0),) * 4 + ((0, pad * ps),))

    idx_c = jnp.moveaxis(idx_p.reshape(B, nc, cpp), 1, 0)
    # keep is [B,N,G,nP] (pooled) or [B,N,G,Sq,nP] (per-query verify)
    keep_c = jnp.moveaxis(keep_p.reshape(*keep.shape[:-1], nc, cpp), -2, 0)
    valid_c = jnp.moveaxis(
        valid_p.reshape(B, 1, 1, Sq, nc, cpp * ps), 4, 0)

    m0 = jnp.full((B, N, G, Sq), _NEG, F32)
    l0 = jnp.zeros((B, N, G, Sq), F32)
    a0 = jnp.zeros((B, N, G, Sq, hd), F32)

    def body(carry, xs):
        m, l, acc = carry
        idx_i, keep_i, valid_i = xs
        if k_scale is not None:
            k_i = _dequant_pages(gather_pages(k_pool, idx_i, layer), k_scale[idx_i])
            v_i = _dequant_pages(gather_pages(v_pool, idx_i, layer), v_scale[idx_i])
            k_i = k_i.reshape(B, cpp * ps, N, hd)
            v_i = v_i.reshape(B, cpp * ps, N, hd)
        else:
            k_i = gather_pages(k_pool, idx_i, layer).reshape(B, cpp * ps, N, hd)
            v_i = gather_pages(v_pool, idx_i, layer).reshape(B, cpp * ps, N, hd)
        kq_i, _, fk_i = _fixed_split(k_i, hdp)
        s = jnp.einsum("bngqh,bsnh->bngqs", qq, kq_i,
                       preferred_element_type=F32)
        if hdp.approx:
            s = s - jnp.einsum("bngqh,bsnh->bngqs", fq, fk_i,
                               preferred_element_type=F32)
        s = s * scale
        keep_e = _expand_keep(keep_i, ps, valid_i, s.ndim)
        s = jnp.where(keep_e, s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(keep_e, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        pv = jnp.einsum("bngqs,bsnh->bngqh", p.astype(v_i.dtype), v_i,
                        preferred_element_type=F32)
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    (_, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (idx_c, keep_c, valid_c))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return _head_gate(out, head_kept)


def _paged_fum_kernel_stage3(qq, k_pool, v_pool, table, keep, head_kept,
                             q_pos, fetched, *, hdp: HDPConfig, ps: int,
                             k_scale=None, v_scale=None, layer=None):
    """Stage 2+3 through the gather-free Pallas kernel.

    Compresses the OR-over-heads (and, for multi-query verify, OR-over-
    query-rows) page fetch list to (pool page ids, logical slot
    positions, counts) — the scalar-prefetch arrays from which the
    kernel DMAs each slot's kept pages, a compute block of pages at a
    time, straight from the pool: pruned pages and table columns past a
    row's count are never DMA'd (no gathered intermediate at all). The
    per-head, per-row keep of each listed page reaches the kernel in list
    order ([B, N, G, Sq, mk], through VMEM). A verify call streams each
    surviving page once for ALL Sq query rows — the pool is read once per
    round.
    """
    from repro.kernels.hdp_paged_decode import hdp_paged_fum_decode
    from repro.kernels.ops import _auto_interpret

    B, N, G, Sq, hd = qq.shape
    nP = table.shape[1]
    # normalize to the per-query-row shapes the kernel consumes (pooled
    # decode masks broadcast over the single query row)
    keep_q = keep if keep.ndim == 5 else keep[..., None, :]
    keep_q = jnp.broadcast_to(keep_q, (B, N, G, Sq, nP))
    # kept pages in ascending logical order (monotone pool DMA), padded
    # with the scratch page past each row's count
    big = jnp.iinfo(jnp.int32).max
    key = jnp.where(fetched, jnp.arange(nP, dtype=jnp.int32)[None], big)
    logical = jnp.sort(key, axis=-1)
    counts = fetched.sum(-1).astype(jnp.int32)
    in_range = jnp.arange(nP)[None] < counts[:, None]
    logical = jnp.where(in_range, logical, 0)
    page_ids = jnp.where(in_range,
                         jnp.take_along_axis(table, logical, axis=1), 0)
    keep_sel = jnp.take_along_axis(
        keep_q, logical[:, None, None, None, :], axis=-1)
    # row 0's extent; the kernel adds the query index (consecutive rows)
    kv_len = (q_pos.reshape(B, Sq)[:, 0] + 1).astype(jnp.int32)
    out = hdp_paged_fum_decode(
        qq, k_pool, v_pool, page_ids, logical, counts,
        keep_sel, kv_len, approx=hdp.approx, int_bits=hdp.int_bits,
        frac_bits=hdp.frac_bits, k_scale=k_scale, v_scale=v_scale,
        layer=layer, interpret=_auto_interpret(None))
    return _head_gate(out, head_kept)


def _paged_scout_route(stage3, k_pool, hdp: HDPConfig, *, kv_scale, window,
                       n_q, per_query, draft) -> bool:
    """Whether stage 1 of a paged decode call runs as the paged scout
    kernel: where stage 3 is the paged kernel, over an int8 pool on the
    static grid, for one causal query row per slot with no draft and no
    window. Verify rows, drafts, sliding windows, calibrated scales and
    fp32 / bf16 pools keep the XLA scout."""
    return (stage3 == "pallas_paged" and k_pool.dtype == jnp.int8
            and kv_scale != "absmax" and not window and n_q == 1
            and not per_query and draft is None and hdp.causal)


def hdp_paged_decode_attention(q, k_pool, v_pool, ik_pool, table, *,
                               q_pos, k_pos, hdp: HDPConfig, window: int = 0,
                               return_stats: bool = False,
                               stage3: str = "xla", page_chunk: int = 128,
                               draft=None, per_query: bool = False,
                               fk_pool=None, k_scale=None, v_scale=None,
                               kv_scale: str = "grid", layer=None):
    """HDP decode over a block-paged KV cache — the FUM dataflow in XLA.

    q [B,N,G,Sq,hd]; k/v_pool [P,N,ps,hd] head-major page pools (page 0
    is the reserved scratch page); ik_pool [P,N,ps,hd] int8 scout copy
    of K; table [B,nP] int32 page table (0-padded). With ``layer`` (an
    int32 scalar) every pool and scale array is the layer-stacked one
    ([L, ...]) and that layer is read: pages are gathered straight from
    the stack and the Pallas kernel DMAs from it, so no per-layer copy of
    the pool is made.

    An int8 ``k_pool`` switches on the quantized-pool path:
    ``k_scale``/``v_scale`` [P, N] carry the per-page scales, ``ik_pool``
    and ``fk_pool`` are ignored — the integer and fraction scout copies
    are *derived as views of the codes* (finite even for poisoned
    pages/positions, like the separate fp32-pool copies they replace) —
    and every stage-3 consumer dequantizes in place of its gather, so
    pruned pages still never DMA. Decoded values land exactly on the
    fixed-point grid stage 3 snaps K to, so the downstream maths is
    shared verbatim with the fp32 path.

    Stage 1 streams the int8 scout copy for EVERY allocated page (the
    paper's always-read integer pass), pools it into per-page importances
    and derives the keep mask + early head gate (core.hdp.decode_scout).
    Under ``stage3="pallas_paged"`` a plain decode step (one causal query
    row, no draft) over an int8 static-grid pool pools its importances
    in the paged scout kernel instead (``kernels/hdp_paged_scout.py``:
    only each slot's columns up to its extent are DMA'd, no score slab
    is made); both paths decide through ``core.hdp.scout_decision``, so
    the keep mask is the same bit for bit. Stage 2 fetches full-precision
    K/V only for surviving pages — pruned pages' gather indices are
    redirected to the scratch page, so their memory is never touched (the
    TPU kernel analogue never DMAs them).
    Stage 3 runs the approximate attention QK^T - FQ FK^T on the fetched
    pages with the keep mask excluded from the softmax.

    ``stage3`` selects the 2+3 implementation (backend selection lives in
    ``repro.attention``; this function is the shared stage pipeline):

    * ``"xla"`` — contexts up to ``page_chunk`` columns gather kept pages
      into one contiguous slab (exactly the dense reduction order);
      longer contexts run an online-softmax scan over page chunks, so
      stage-2 memory stays O(page_chunk) instead of O(Sk).
    * ``"pallas_paged"`` — the gather-free FUM kernel: scalar-prefetched
      page ids index the pool directly (interpret mode off-TPU).

    ``kv_scale="absmax"`` (quantized pools only) reads per-page
    *calibrated* scales instead of assuming the static power-of-two
    grid: stage 1 dequantizes the scout stream through a sanitized copy
    of ``k_scale`` (NaN freed-page poison -> the static step, poison
    codes -> 0, so the scout stays finite exactly as on the static
    grid), and the stage-3 consumers already dequantize through the
    gathered scales. The FUM kernel derives its scout from the static
    grid, so ``stage3="pallas_paged"`` falls back to "xla" here (as it
    does for a sliding window; the registry never routes either call to
    the kernel, and these fallbacks keep direct calls correct).

    ``per_query`` runs the scout per query row (the multi-query verify
    shape: each of the Sq rows computes the keep mask / head gate its own
    single-token step would); ``draft`` (a DraftProfile — thresholds
    already overlaid into ``hdp``) switches stage 3 to the draft score
    source, under which the full-precision K pool is NEVER read: the
    scores come from the int8 scout copy stage 1 streams anyway, and only
    surviving pages' V is fetched.
    """
    B, N, G, Sq, hd = q.shape
    ps = k_pool.shape[-2]
    nP = table.shape[1]
    Sk = nP * ps
    scale = 1.0 / (hd ** 0.5)
    quantized = k_pool.dtype == jnp.int8
    absmax = quantized and kv_scale == "absmax"

    if stage3 != "xla" and window:
        # the kernels' per-row validity is an upper bound (cols < kv_len)
        # and cannot express the sliding-window lower bound; fall back to
        # the jnp path rather than silently attending out-of-window keys
        stage3 = "xla"
    if stage3 == "pallas_paged" and absmax:
        # the FUM kernel derives its in-register scout from the STATIC
        # grid; under calibrated scales that scout would disagree with
        # the one above — fall back rather than fork the keep mask
        stage3 = "xla"
    if layer is not None:
        if k_scale is not None:
            k_scale, v_scale = k_scale[layer], v_scale[layer]
        if stage3 != "pallas_paged" or (draft is not None
                                        and draft.scores != "approx"):
            # the XLA stages read one layer's slice: a gather straight
            # from the stack lets XLA re-lay the whole carried pool out
            # for it (hd 80 puts the page's 128 positions in lanes),
            # copying every layer in and out of the decode program
            k_pool, v_pool, ik_pool, fk_pool = (
                None if x is None else x[layer]
                for x in (k_pool, v_pool, ik_pool, fk_pool))
            layer = None

    paged_scout = _paged_scout_route(stage3, k_pool, hdp, kv_scale=kv_scale,
                                     window=window, n_q=Sq,
                                     per_query=per_query, draft=draft)
    kernel_stats = {}       # what the Pallas paged kernels walk, if they run
    # ---- stage 1: integer scout on the always-streamed int8 copy ----
    with jax.named_scope("hdp.scout"):
        qq, iq, fq = _fixed_split(q, hdp)
        if paged_scout:
            from repro.kernels.hdp_paged_scout import hdp_paged_scout
            from repro.kernels.ops import _auto_interpret
            # causal single-row decode: positions 0 .. q_pos are valid
            kv_len = jnp.clip(q_pos.reshape(B) + 1, 0, Sk).astype(jnp.int32)
            theta, walked = hdp_paged_scout(
                iq[..., 0, :], k_pool, table, kv_len, int_bits=hdp.int_bits,
                layer=layer, interpret=_auto_interpret(None))
            bvalid = (jnp.arange(nP) * ps < kv_len[:, None])[:, None, None]
            keep, bvalid, theta, theta_head, head_kept = scout_decision(
                theta, bvalid, kv_len[:, None, None], hdp)
            kernel_stats["scout_pages"] = walked.astype(F32)
        else:
            if absmax:
                # calibrated scales: dequantize the scout stream through a
                # sanitized scale copy — poison codes -> 0 and NaN
                # freed-page scales -> the static step, preserving the
                # scout-always-finite contract of the static-grid view
                codes = gather_pages(k_pool, table, layer)  # [B,nP,ps,N,hd]
                ksc = k_scale[table]                             # [B,nP,N]
                ksc = jnp.where(jnp.isfinite(ksc), ksc,
                                pool_scale(hdp.int_bits))
                cf = jnp.where(codes == POISON_CODE, 0, codes).astype(F32)
                k_fin = (cf * ksc[:, :, None, :, None]).reshape(B, Sk, N, hd)
                ik = jnp.trunc(k_fin)
            elif quantized:
                # the pool's codes ARE the scout stream: the finite
                # static-grid view (poison sentinels -> 0, masked anyway)
                # truncates to the same integer parts the fp32 pools'
                # write-time copy stored
                k_fin = pool_view_finite(gather_pages(k_pool, table, layer),
                                         hdp.int_bits)
                k_fin = k_fin.reshape(B, Sk, N, hd)
                ik = jnp.trunc(k_fin)
            else:
                ik = gather_pages(ik_pool, table, layer).reshape(
                    B, Sk, N, hd).astype(F32)
            s_int = jnp.einsum("bngqh,bsnh->bngqs", iq, ik,
                               preferred_element_type=F32)
            valid = _mask_bias(q_pos, k_pos, hdp.causal, window)
            keep, bvalid, theta, theta_head, head_kept = decode_scout(
                s_int, valid, hdp, per_query=per_query)

    with jax.named_scope("hdp.attend"):
        # ---- stage 2: fetch-upon-mask page selection ----
        # page fetch granularity is OR-over-heads (a page holds all kv heads)
        # and, under multi-query verify, OR-over-query-rows (the pool is read
        # once per round); the per-head/per-row keep mask still applies inside
        # the softmax below. Early head-gated heads (output zeroed) don't
        # demand their pages at all.
        fetched = (keep & head_kept[..., None]).any(
            axis=tuple(range(1, keep.ndim - 1)))                  # [B, nP]

        if draft is not None and draft.scores != "approx":
            # draft stage 3: scores from the int8 scout copies — s_int alone
            # ("int") or QQ·IK + IQ·FK^ ("scout": the quantized-fraction copy
            # recovers the exact pass's scores to within its 2^-6 grid);
            # k_pool is never touched, and V is gathered only for surviving
            # pages (scratch-redirect)
            s = s_int
            if draft.scores == "scout":
                if quantized:
                    # the fraction view comes straight off the codes (exact:
                    # the coarse pool grid is a subset of the 2^-6 scout
                    # grid), so no separate fraction pool exists to read
                    fkh = k_fin - ik
                elif fk_pool is None:
                    # the IQ·FK^ term cannot be derived without reading the
                    # full-precision pool — which is exactly what this score
                    # mode promises never to do; surface the misuse instead
                    # of silently serving lower-fidelity drafts
                    raise ValueError(
                        'draft scores="scout" needs the f_scout pool '
                        "(PagedKVCache(draft_scout=True)); pass fk_pool or "
                        'use scores="int"')
                else:
                    fkh = gather_pages(fk_pool, table, layer).reshape(
                        B, Sk, N, hd).astype(F32) \
                        / FRAC_SCOUT_SCALE
                s = s + jnp.einsum("bngqh,bsnh->bngqs", fq, ik,
                                   preferred_element_type=F32) \
                      + jnp.einsum("bngqh,bsnh->bngqs", iq, fkh,
                                   preferred_element_type=F32)
            gather_idx = jnp.where(fetched, table, 0)         # pruned -> scratch
            if quantized:
                v = _dequant_pages(gather_pages(v_pool, gather_idx, layer),
                                   v_scale[gather_idx])
                v = v.reshape(B, Sk, N, hd)
            else:
                v = gather_pages(v_pool, gather_idx, layer).reshape(B, Sk, N, hd)
            out = _approx_block_attention(None, None, None, None, v, keep, valid,
                                          head_kept, block_k=ps, scale=scale,
                                          approx=False, scores=s)
        elif stage3 == "pallas_paged":
            from repro.kernels.hdp_paged_decode import pages_per_block
            ppb = pages_per_block(nP, k_pool, v_pool)
            kernel_pages = fetched.sum(-1).astype(F32)
            kernel_stats.update(kernel_pages=kernel_pages,
                                kernel_block_pages=jnp.ceil(
                                    kernel_pages / ppb) * ppb)
            out = _paged_fum_kernel_stage3(qq, k_pool, v_pool, table, keep,
                                           head_kept, q_pos, fetched,
                                           hdp=hdp, ps=ps,
                                           k_scale=k_scale if quantized else None,
                                           v_scale=v_scale if quantized else None,
                                           layer=layer)
        else:
            gather_idx = jnp.where(fetched, table, 0)         # pruned -> scratch
            cpp = max(1, page_chunk // ps)                    # pages per chunk
            if nP <= cpp:
                # one chunk covers the context: gather kept pages into a slab
                # and reduce exactly like the dense-layout decode (keeps paged
                # and dense engines token-identical on short contexts)
                if quantized:
                    k = _dequant_pages(gather_pages(k_pool, gather_idx, layer),
                                       k_scale[gather_idx])
                    v = _dequant_pages(gather_pages(v_pool, gather_idx, layer),
                                       v_scale[gather_idx])
                    k = k.reshape(B, Sk, N, hd)
                    v = v.reshape(B, Sk, N, hd)
                else:
                    k = gather_pages(k_pool, gather_idx, layer).reshape(B, Sk, N, hd)
                    v = gather_pages(v_pool, gather_idx, layer).reshape(B, Sk, N, hd)
                kq, _, fk = _fixed_split(k, hdp)
                out = _approx_block_attention(qq, fq, kq, fk, v, keep, valid,
                                              head_kept, block_k=ps, scale=scale,
                                              approx=hdp.approx)
            else:
                out = _paged_scan_attention(qq, fq, k_pool, v_pool, gather_idx,
                                            keep, valid, head_kept, hdp=hdp,
                                            ps=ps, cpp=cpp, scale=scale,
                                            k_scale=k_scale if quantized else None,
                                            v_scale=v_scale if quantized else None,
                                            layer=layer)

    stats = None
    if return_stats:
        alloc = jnp.maximum((table > 0).astype(F32).sum(-1), 1.0)   # [B]
        stats = {**_block_sparsity_stats(keep, bvalid, head_kept),
                 "page_sparsity": 1.0 - jnp.minimum(
                     (fetched & (table > 0)).astype(F32).sum(-1) / alloc, 1.0),
                 "theta_head": theta_head, **kernel_stats}
    return out.astype(q.dtype), stats


# --------------------------------------------------------------- full layer
def build_attn_call(cfg, *, mode: str, paged: bool = False,
                    per_slot: bool = False, self_aligned: bool = False,
                    cross: bool = False, causal: bool = True,
                    collect_stats: bool = False, draft=None,
                    verify: bool = False,
                    kv_scale: str = "grid") -> AttnCall:
    """Construct the AttnCall `attn_apply` dispatches on.

    One place derives the static call descriptor from the model config and
    invocation shape — `attn_apply` uses it for dispatch, and the serving
    engine uses the SAME function to report the resolved backend per
    phase, so the report cannot drift from the dispatch.

    ``draft`` (a DraftProfile) marks a self-speculative draft step: its
    threshold overrides are folded into the call's HDP config here, so
    backends see exactly the grid the draft attends with. ``verify``
    marks a multi-query verify call (Sq > 1 decode — per-query-row scout
    semantics required of HDP backends).
    """
    hdp = cfg.hdp
    use_hdp = (hdp is not None and hdp.enabled
               and (mode != "train" or hdp.apply_in_training))
    eff_causal = causal and not cross
    window = 0 if cross else cfg.sliding_window
    hdp_eff = hdp.replace(causal=eff_causal) if use_hdp else None
    if draft is not None and hdp_eff is not None:
        hdp_eff = draft.overlay(hdp_eff)
    return AttnCall(
        mode="decode" if mode == "decode" else "prefill",
        layout="paged" if paged else "dense",
        causal=eff_causal,
        window=window,
        hdp=hdp_eff,
        per_slot=per_slot,
        self_aligned=self_aligned,
        trainable=mode == "train",
        chunk=cfg.attn_chunk,
        needs_stats=collect_stats,
        draft=draft if use_hdp else None,
        verify=verify and mode == "decode",
        kv_scale=kv_scale if paged else "grid",
    )


def attn_apply(cfg, p, x, *, mode: str, positions, cache=None,
               enc_out=None, causal: bool = True, static_cache: bool = False,
               collect_stats: bool = False, page_table=None,
               write_floor=None, draft=None,
               attn: Optional[AttnSpec] = None) -> Tuple[Any, Any, Any]:
    """Full MHA layer: project, rope, (HDP-)attend, output-project.

    mode: train | prefill | decode. cache: {"k","v"} [B,Smax,N,hd] (+ pos
    handled by caller passing `positions`). enc_out: cross-attention keys
    source (whisper decoder prefill); static_cache: attend to the cache
    as-is without writing (whisper cross-attn at decode). write_floor
    [B]: per-slot first-owned-page offset into the page table — a paged
    decode write whose page column sits below the floor would land in a
    *shared read-only* prefix page and is redirected to the scratch page
    instead (the prefix cache's immutability fence; the engine's COW
    keeps the fence un-hit in normal operation). draft: DraftProfile of a
    self-speculative draft step (None for full-fidelity calls). attn:
    backend selection spec (None -> the default spec, which honors the
    REPRO_ATTN_BACKEND env var); the attention maths itself is dispatched
    through ``repro.attention.attention`` on an AttnCall descriptor.
    Returns (y, new_cache, stats|None).

    Decode calls with S > 1 are multi-query *verify* calls (speculative
    decode): ``positions[:, j]`` must be consecutive per slot, every row's
    K/V is scattered into the cache before attention reads it, and HDP
    backends run their scout per query row.

    NOTE (perf log B3): writing K/V into the *stacked* [L,B,S,N,hd] cache
    before reading (to dodge the per-layer carry copy) was measured and
    REFUTED — two dynamic indices on a sequence-sharded buffer make the
    SPMD partitioner reshard the cache to replicated (memory_t 0.33 s ->
    2.6 s). The per-layer slice+update carry in transformer._stack is the
    best measured point.
    """
    B, S, D = x.shape
    H, N, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // N

    with jax.named_scope("attn.proj"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        if cfg.qk_norm:
            q = L.rms_norm(q, p["q_norm"])
        q = shd(q, "batch", "seq_act", "heads_act", None)
        if cfg.pos_emb == "rope" and enc_out is None and not static_cache:
            q = L.apply_rope(q, positions, cfg.rope_theta)

    new_cache = cache
    if static_cache:
        # cross-attention at decode: keys were cached at prefill
        k_full, v_full = cache["k"], cache["v"]
        k_pos = jnp.arange(k_full.shape[1])
    else:
        with jax.named_scope("attn.proj"):
            kv_src = enc_out if enc_out is not None else x
            k = jnp.einsum("bsd,dnk->bsnk", kv_src, p["wk"])
            v = jnp.einsum("bsd,dnk->bsnk", kv_src, p["wv"])
            if cfg.qkv_bias:
                k, v = k + p["bk"], v + p["bv"]
            if cfg.qk_norm:
                k = L.rms_norm(k, p["k_norm"])
            k = shd(k, "batch", "seq_act", "kv_heads", None)
            if cfg.pos_emb == "rope" and enc_out is None:
                k = L.apply_rope(k, positions, cfg.rope_theta)

        if (attn is not None and attn.kv_dtype in ("int8", "fp8_v")
                and getattr(attn, "kv_scale", "grid") != "absmax"
                and mode == "prefill" and enc_out is None
                and cache is not None and "k_pages" not in cache):
            # quantized-pool engine prefilling its dense REQUEST cache:
            # round-trip K/V through the pool grid BEFORE the write, so
            # prefill attention (which reads this cache), the pool insert
            # (exact encode of these values), prefix-cache gathers and
            # COW tails all see one set of values — hot and cold runs
            # stay token-identical, and only the fp32-vs-int8 A/B sees
            # quantization drift. Calibrated (absmax) pools skip this:
            # their per-page scales depend on the values actually
            # inserted, so no write-time snap can anticipate them —
            # hot/cold bit parity is forfeited by that mode's contract
            # and the fp32 drift gate bounds the error instead
            with jax.named_scope("kv.write"):
                ib = pool_int_bits(cfg.hdp)
                k = roundtrip_pool(k, ib).astype(k.dtype)
                if attn.kv_dtype == "fp8_v":
                    v = v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                else:
                    v = roundtrip_pool(v, ib).astype(v.dtype)

        if cache is not None and "k_pages" in cache:
            # block-paged serving cache (decode only): write the S
            # tokens' K/V (+ int8 scout copy) into their slots' pages
            # (S > 1 = speculative verify — one write, then one
            # attention over the pool), then attend through the table.
            # A layer-stacked cache (it carries its "layer" index) is
            # written and read in place: no per-layer slice is copied.
            assert mode == "decode" and positions.ndim == 2, \
                "paged cache is a decode-time serving layout"
            with jax.named_scope("kv.write"):
                li = cache.get("layer")
                ps = cache["k_pages"].shape[-2]
                nP = page_table.shape[1]
                pidx = resolve_write_pages(positions, page_table, ps,
                                           write_floor)
                off = positions % ps
                pool_q = cache["k_pages"].dtype == jnp.int8
                kv_scale = getattr(attn, "kv_scale", "grid") if attn else "grid"
                if pool_q and kv_scale == "absmax":
                    # calibrated pool: encode against the destination page's
                    # CURRENT scale (set by the prefill insert; fresh decode
                    # pages keep the static step), sanitizing NaN freed-page
                    # poison back to the static step so the encode is finite
                    ib = pool_int_bits(cfg.hdp)
                    s0 = pool_scale(ib)
                    sc = layer_view(cache)
                    ks = sc["k_scale"][pidx]                       # [B,S,N]
                    ks = jnp.where(jnp.isfinite(ks), ks, s0)[..., None]
                    k_store = encode_pool_scaled(k, ks)
                    if cache["v_pages"].dtype != jnp.int8:
                        v_store = v.astype(cache["v_pages"].dtype)
                    else:
                        vs = sc["v_scale"][pidx]
                        vs = jnp.where(jnp.isfinite(vs), vs, s0)[..., None]
                        v_store = encode_pool_scaled(v, vs)
                elif pool_q:
                    ib = pool_int_bits(cfg.hdp)
                    k_store = encode_pool(k, ib)
                    v_store = (v.astype(cache["v_pages"].dtype)
                               if cache["v_pages"].dtype != jnp.int8
                               else encode_pool(v, ib))
                else:
                    k_store = k.astype(cache["k_pages"].dtype)
                    v_store = v.astype(cache["v_pages"].dtype)
                new_cache = {**cache,
                             "k_pages": write_pages(cache["k_pages"], pidx, off,
                                                    k_store, li),
                             "v_pages": write_pages(cache["v_pages"], pidx, off,
                                                    v_store, li)}
                if not pool_q and draft is not None \
                        and draft.scores != "approx" \
                        and cfg.hdp is not None and cfg.hdp.enabled:
                    # a scout-scores draft neither reads nor needs the
                    # full-precision K it would stage: later draft steps
                    # score against the scout copies, and the verify rewrites
                    # every staged position with exact K before anything else
                    # can read it — skip the dead scatter. Gated on HDP like
                    # the call descriptor (build_attn_call nulls draft
                    # without a scout): the HDP-off degraded draft runs
                    # exact attention and DOES read this K. A QUANTIZED pool
                    # inverts the optimization: the codes ARE the scout copy
                    # later draft steps stream, so the scatter is live
                    new_cache["k_pages"] = cache["k_pages"]
                if "k_scout" in cache:
                    new_cache["k_scout"] = write_pages(
                        cache["k_scout"], pidx, off, scout_int8(k, cfg.hdp), li)
                if "f_scout" in cache:
                    new_cache["f_scout"] = write_pages(
                        cache["f_scout"], pidx, off, scout_frac_int8(k, cfg.hdp),
                        li)
            ar = jnp.arange(nP * ps)
            k_pos = jnp.where(ar[None, :] <= positions[:, -1:], ar, -1)
            k_pos = k_pos[:, None, None, :]              # [B,1,1,nP*ps]
            k_full = v_full = None  # gathered lazily (FUM) below
        elif cache is not None:
            if positions.ndim == 2 and enc_out is None:
                # per-slot positions (continuous batching): each sequence
                # writes its cache at its own offset
                with jax.named_scope("kv.write"):
                    def upd(c, kv, p0):
                        return jax.lax.dynamic_update_slice_in_dim(c, kv, p0, 0)
                    new_cache = {
                        "k": jax.vmap(upd)(cache["k"], k.astype(cache["k"].dtype),
                                           positions[:, 0]),
                        "v": jax.vmap(upd)(cache["v"], v.astype(cache["v"].dtype),
                                           positions[:, 0]),
                    }
                k_full, v_full = new_cache["k"], new_cache["v"]
                ar = jnp.arange(k_full.shape[1])
                k_pos = jnp.where(ar[None, :] <= positions[:, -1:], ar, -1)
                k_pos = k_pos[:, None, None, :]          # [B,1,1,Smax]
            else:
                with jax.named_scope("kv.write"):
                    pos0 = positions[0] if enc_out is None else 0
                    new_cache = {
                        "k": jax.lax.dynamic_update_slice_in_dim(
                            cache["k"], k.astype(cache["k"].dtype), pos0, 1),
                        "v": jax.lax.dynamic_update_slice_in_dim(
                            cache["v"], v.astype(cache["v"].dtype), pos0, 1),
                    }
                k_full, v_full = new_cache["k"], new_cache["v"]
                k_pos = jnp.arange(k_full.shape[1])
                if enc_out is None:
                    k_pos = jnp.where(k_pos <= positions[-1], k_pos, -1)
        else:
            k_full, v_full = k, v
            k_pos = (jnp.arange(k.shape[1]) if enc_out is not None
                     else positions)

    qg = q.reshape(B, S, N, G, hd).transpose(0, 2, 3, 1, 4)  # [B,N,G,S,hd]
    # per-slot positions carry a batch dim; align it with [B,N,G,Sq,Sk]
    q_pos = positions[:, None, None, :] if positions.ndim == 2 else positions

    is_cross = enc_out is not None or static_cache
    paged = cache is not None and "k_pages" in cache
    call = build_attn_call(
        cfg, mode=mode, paged=paged, per_slot=positions.ndim == 2,
        self_aligned=(cache is None and not is_cross and positions.ndim == 1),
        cross=is_cross, causal=causal, collect_stats=collect_stats,
        draft=draft if mode == "decode" else None,
        verify=mode == "decode" and S > 1 and not is_cross,
        kv_scale=getattr(attn, "kv_scale", "grid") if attn else "grid")
    mesh = None
    if paged:
        from repro.distribution.tp import active_serving_mesh
        mesh = active_serving_mesh()
    if mesh is not None:
        # tensor-parallel serving: run the paged-decode dispatch head-
        # sharded under the ambient mesh (per-shard scout + fetched set;
        # one exact all-gather of o before the projection below)
        from repro.distribution.tp import tp_paged_attention
        o, stats = tp_paged_attention(
            qg, call, attn, q_pos=q_pos, k_pos=k_pos, cache=new_cache,
            page_table=page_table, mesh=mesh)
    else:
        o, stats = attention(
            qg, k_full, v_full, call, spec=attn, q_pos=q_pos, k_pos=k_pos,
            cache=new_cache if paged else None, page_table=page_table)

    with jax.named_scope("attn.proj"):
        o = o.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
        y = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        y = shd(y, "batch", "seq_act", "embed_act")
    return y, new_cache, stats
