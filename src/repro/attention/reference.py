"""The ``reference`` backend: materializing oracle for every call shape.

Generalizes ``core.hdp.hdp_attention_reference`` (the paper's Algorithm 2
transliteration, single-head [..., L, d]) to the model tensor layout
(q [B,N,G,Sq,hd]; k/v [B,Sk,N,hd]) and to every call the registry can
describe: prefill and decode, dense and paged layouts, causal/window
masks, per-slot positions, HDP on or off. Everything is computed densely
with explicit masks — no scans, no kernels, no fetch-upon-mask gather —
so it is the conformance ground truth each production backend is tested
against, and the slowest-but-safest fallback of the auto chain.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.attention.registry import register_backend
from repro.attention.spec import AttnCall
from repro.attention.stats import AttnStats
from repro.core import blocking
from repro.core.hdp import calibrated_split, decode_scout

F32 = jnp.float32


def _supports(call: AttnCall) -> bool:
    del call
    return True  # the oracle serves every valid AttnCall


def _pad_axis(x, axis, target):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, pad)
    return jnp.pad(x, w)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_pos(pos, target):
    """Pad a position array along its last axis; pads become -1 (invalid)."""
    return _pad_axis(pos + 1, pos.ndim - 1, target) - 1


def _densify(cache, page_table, int_bits=4):
    """Gather the FULL page pools into contiguous [B, nP*ps, N, hd] tensors.

    The oracle reads everything — fetch-upon-mask is a performance
    property of the production backends, not part of the semantics; the
    keep mask excludes pruned pages from the softmax either way.
    ``int_bits`` is the pool grid of a quantized cache (ignored for fp32
    pools): it fixes the static scale of the derived integer scout view.
    """
    from repro.models.attention import layer_view
    cache = layer_view(cache)          # a layer-stacked pool -> its layer
    kp, vp = cache["k_pages"], cache["v_pages"]
    B, nP = page_table.shape
    N, ps, hd = kp.shape[1], kp.shape[2], kp.shape[3]

    def pages(pool):  # head-major [P, N, ps, hd] -> [B, nP, ps, N, hd]
        return jnp.swapaxes(pool[page_table], 2, 3)

    if kp.dtype == jnp.int8:
        # quantized pool: dequantize through the per-page scales (poison
        # sentinel -> NaN, exactly like the production stage 3) and
        # derive the integer scout view from the codes (poison -> 0,
        # exactly like the production stage 1)
        from repro.core.quant import decode_pool, pool_view_finite
        ks = cache["k_scale"][page_table][:, :, None, :, None]
        vs = cache["v_scale"][page_table][:, :, None, :, None]
        k = decode_pool(pages(kp), ks).reshape(B, nP * ps, N, hd)
        vg = pages(vp)
        v = (vg.astype(F32) * vs if vg.dtype != jnp.int8
             else decode_pool(vg, vs)).reshape(B, nP * ps, N, hd)
        ik = jnp.trunc(pool_view_finite(pages(kp), int_bits).reshape(
            B, nP * ps, N, hd))
        return k, v, ik
    k = pages(kp).reshape(B, nP * ps, N, hd)
    v = pages(vp).reshape(B, nP * ps, N, hd)
    ik = None
    if "k_scout" in cache:
        ik = pages(cache["k_scout"]).reshape(B, nP * ps, N, hd).astype(F32)
    return k, v, ik


def _sparsity_stats(keep, bvalid, head_kept):
    kept = (keep & bvalid).astype(F32).sum()
    tot = jnp.maximum(
        jnp.broadcast_to(bvalid, keep.shape).astype(F32).sum(), 1.0)
    return (1.0 - kept / tot, 1.0 - head_kept.astype(F32).mean())


def _sparsity_stats_per_slot(keep, bvalid, head_kept):
    """Decode-mode stats keep the batch dim ([B] leaves), mirroring the
    production backends, so the serving engine can mask parked slots."""
    ax = tuple(range(1, keep.ndim))
    kept = (keep & bvalid).astype(F32).sum(ax)
    tot = jnp.maximum(
        jnp.broadcast_to(bvalid, keep.shape).astype(F32).sum(ax), 1.0)
    hax = tuple(range(1, head_kept.ndim))
    return (1.0 - kept / tot, 1.0 - head_kept.astype(F32).mean(hax))


def _dense_exact(q, k, v, valid):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bngqh,bsnh->bngqs", q.astype(F32), k.astype(F32),
                   preferred_element_type=F32) * scale
    p = blocking.masked_softmax(s, valid)
    return jnp.einsum("bngqs,bsnh->bngqh", p, v.astype(F32),
                      preferred_element_type=F32)


def _hdp_prefill(q, k, v, call, q_pos, k_pos):
    """Blockwise scout on the (bq x bk) grid — Algorithm 2, fully dense."""
    hdp = call.hdp
    B, N, G, Sq, hd = q.shape
    Sk = k.shape[1]
    bq, bk = hdp.block_q, hdp.block_k
    Sqp, Skp = _ceil_to(Sq, bq), _ceil_to(Sk, bk)
    scale = 1.0 / (hd ** 0.5)

    sq, qq, iq, fq = calibrated_split(_pad_axis(q, 3, Sqp).astype(F32), hdp)
    sk, kq, ik, fk = calibrated_split(_pad_axis(k, 1, Skp).astype(F32), hdp)
    vp = _pad_axis(v, 1, Skp)
    from repro.models.attention import _mask_bias
    valid = _mask_bias(_pad_pos(q_pos, Sqp), _pad_pos(k_pos, Skp),
                       call.causal, call.window)

    s_int = jnp.einsum("bngqh,bsnh->bngqs", iq, ik,
                       preferred_element_type=F32)
    theta = blocking.block_abs_sum(jnp.where(valid, s_int, 0.0), bq, bk)
    bvalid = blocking.block_abs_sum(valid.astype(F32), bq, bk) > 0
    if hdp.block_pruning:
        thr = blocking.row_threshold(theta, hdp.rho_b, bvalid)
        keep = blocking.block_keep_mask(theta, thr, bvalid)
    else:
        keep = jnp.broadcast_to(bvalid, theta.shape)

    theta_head = jnp.where(bvalid, theta, 0.0).sum(axis=(-2, -1))
    if hdp.normalize_head_score:
        n_valid = valid.astype(F32).sum(axis=(-2, -1))
        theta_head = theta_head / jnp.maximum(n_valid, 1.0)
    head_kept = (theta_head > hdp.tau_h) if hdp.head_pruning \
        else jnp.ones_like(theta_head, bool)

    s = jnp.einsum("bngqh,bsnh->bngqs", qq, kq, preferred_element_type=F32)
    if hdp.approx:
        s = s - jnp.einsum("bngqh,bsnh->bngqs", fq, fk,
                           preferred_element_type=F32)
    s = s * (scale / (sq * sk))
    keep_e = blocking.expand_block_mask(keep, bq, bk) & valid
    softmax = (blocking.approx_softmax if hdp.approx_softmax
               else blocking.masked_softmax)
    p = softmax(s, keep_e)
    out = jnp.einsum("bngqs,bsnh->bngqh", p, vp.astype(F32),
                     preferred_element_type=F32)
    out = out[:, :, :, :Sq] * head_kept[..., None, None].astype(F32)

    stats = None
    if call.needs_stats:
        bs, hs = _sparsity_stats(keep, bvalid, head_kept)
        stats = AttnStats(bs, hs, theta_head=theta_head)
    return out, stats


def _hdp_decode(q, k, v, call, q_pos, k_pos, *, ik=None, fixed_grid=False,
                page_table=None):
    """Pooled-row scout over KV blocks/pages (decode_scout semantics).

    ``ik``: pre-quantized integer scout copy of K (paged: stored at cache
    write time); ``fixed_grid`` selects the calibration-free fixed-point
    split the paged backends always operate on. Verify calls
    (``call.verify``) scout per query row; draft calls (``call.draft``)
    switch the score source to the profile's draft approximation — the
    oracle mirrors the production draft semantics exactly, so draft
    conformance is testable backend-to-backend.
    """
    from repro.models.attention import (_expand_keep, _fixed_split,
                                        _head_gate, _mask_bias)
    hdp = call.hdp
    bk = hdp.block_k
    Sk = k.shape[1]
    Skp = _ceil_to(Sk, bk)
    scale = 1.0 / (q.shape[-1] ** 0.5)

    kp = _pad_axis(k, 1, Skp).astype(F32)
    if fixed_grid:
        qq, iq, fq = _fixed_split(q, hdp)
        kq, _, fk = _fixed_split(kp, hdp)
        rescale = 1.0
    else:
        sq, qq, iq, fq = calibrated_split(q.astype(F32), hdp)
        sk, kq, ik_c, fk = calibrated_split(kp, hdp)
        ik = ik_c if ik is None else ik
        rescale = 1.0 / (sq * sk)
    if ik is None:
        ik = _fixed_split(kp, hdp)[1]
    ik = _pad_axis(ik, 1, Skp)
    vp = _pad_axis(v, 1, Skp)

    valid = _mask_bias(q_pos, _pad_pos(k_pos, Skp), call.causal, call.window)
    s_int = jnp.einsum("bngqh,bsnh->bngqs", iq, ik,
                       preferred_element_type=F32)
    keep, bvalid, _, theta_head, head_kept = decode_scout(
        s_int, valid, hdp, per_query=call.verify)

    if call.draft is not None and call.draft.scores != "approx":
        s = s_int
        if call.draft.scores == "scout":
            # QQ·IK + IQ·FK^: the quantized-fraction term re-quantizes FK
            # to the f_scout grid, matching the production pools bit for
            # bit (the write-time copy holds the same rounded values)
            from repro.models.attention import FRAC_SCOUT_SCALE
            fkh = jnp.round(fk * FRAC_SCOUT_SCALE) / FRAC_SCOUT_SCALE
            s = s + jnp.einsum("bngqh,bsnh->bngqs", fq, ik,
                               preferred_element_type=F32) \
                  + jnp.einsum("bngqh,bsnh->bngqs", iq, fkh,
                               preferred_element_type=F32)
    else:
        s = jnp.einsum("bngqh,bsnh->bngqs", qq, kq,
                       preferred_element_type=F32)
        if hdp.approx:
            s = s - jnp.einsum("bngqh,bsnh->bngqs", fq, fk,
                               preferred_element_type=F32)
    s = s * (scale * rescale)
    keep_e = _expand_keep(keep, bk, valid, s.ndim)
    p = blocking.masked_softmax(s, keep_e)
    out = jnp.einsum("bngqs,bsnh->bngqh", p, vp.astype(F32),
                     preferred_element_type=F32)
    out = _head_gate(out, head_kept.astype(F32))

    stats = None
    if call.needs_stats:
        bs, hs = _sparsity_stats_per_slot(keep, bvalid, head_kept)
        page_sp = None
        if page_table is not None:
            fetched = (keep & head_kept[..., None]).any(
                axis=tuple(range(1, keep.ndim - 1)))
            alloc = jnp.maximum((page_table > 0).astype(F32).sum(-1), 1.0)
            page_sp = 1.0 - jnp.minimum(
                (fetched & (page_table > 0)).astype(F32).sum(-1) / alloc, 1.0)
        stats = AttnStats(bs, hs, theta_head=theta_head,
                          page_sparsity=page_sp)
    return out, stats


@register_backend("reference", supports=_supports, priority=0,
                  tags=("reference",))
def run_reference(q, k, v, call: AttnCall, *, q_pos, k_pos, cache=None,
                  page_table=None):
    from repro.models.attention import _mask_bias
    from repro.core.quant import pool_int_bits
    ik = None
    fixed_grid = False
    if call.layout == "paged":
        k, v, ik = _densify(cache, page_table, pool_int_bits(call.hdp))
        fixed_grid = True  # write-time scout copy => static fixed-point grid
    if call.hdp is None:
        valid = _mask_bias(q_pos, k_pos, call.causal, call.window)
        out = _dense_exact(q, k, v, valid)
        return out.astype(q.dtype), None
    if call.mode == "decode":
        out, stats = _hdp_decode(q, k, v, call, q_pos, k_pos, ik=ik,
                                 fixed_grid=fixed_grid,
                                 page_table=page_table)
    else:
        out, stats = _hdp_prefill(q, k, v, call, q_pos, k_pos)
    return out.astype(q.dtype), stats
