"""Hybrid Dynamic Pruning attention — faithful Algorithm 2 + batched fast path.

Two implementations with identical semantics:

* :func:`hdp_attention_reference` — term-by-term transliteration of the
  paper's Algorithm 2 (Integer_atten + Frac1 + Frac2, explicit mask loop
  expressed as array ops). Used as the oracle in tests/benchmarks.
* :func:`hdp_attention` — production path. Uses the algebraic identity
  ``IQ·IKᵀ + IQ·FKᵀ + FQ·IKᵀ == QKᵀ − FQ·FKᵀ`` so the approximation costs
  two MXU matmuls (one shared with the scout), and is fully batched over
  [..., L, D] leading dims. Every leading index is treated as one "head"
  for the head-pruning gate (i.e. per-(batch, head) gating).

Both operate on a single attention head of shape [..., L, d_h]; models vmap
or batch over (batch, heads).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from repro.core import blocking
from repro.core.config import HDPConfig
from repro.core.quant import calib_scale, quantize_and_split


def calibrated_split(x: jnp.ndarray, cfg: HDPConfig):
    """(scale, xq, I, F) with x*scale snapped to the fixed-point grid."""
    s = calib_scale(x, cfg.int_bits, cfg.calib)
    xq, i, f = quantize_and_split(x * s.astype(x.dtype),
                                  cfg.int_bits, cfg.frac_bits)
    return s, xq, i, f


def decode_scout(int_scores: jnp.ndarray, valid: jnp.ndarray, cfg: HDPConfig,
                 per_query: bool = False):
    """Decode-shaped integer scout: one block row per head over KV pages.

    ``int_scores`` [..., Sq, Sk] are integer-part attention scores for a
    (small) decode query group; Sk must be a multiple of ``cfg.block_k``.
    The whole query extent pools into a single row of Sk/block_k blocks —
    with a block-paged KV cache these blocks ARE the cache pages, so the
    keep mask doubles as the page fetch list (Fetch-Upon-Mask). ``valid``
    is a positionally-broadcastable bool mask [..., Sq, Sk].

    ``per_query`` keeps the Sq axis instead of pooling it: each query row
    gets its own block row, importances and head gate, exactly as if it
    had run through ``Sq`` independent single-row scouts. This is the
    speculative-verify shape — row ``j`` of a multi-query verify call
    must reproduce the keep mask its own sequential decode step would
    have computed, or exact-match acceptance loses token identity.

    Returns (keep, bvalid, theta, theta_head, head_kept), where ``[...]``
    below gains a trailing Sq axis when ``per_query``:
      keep [..., nk] bool      — pages that survive block pruning
      bvalid [..., nk] bool    — pages with any valid position
      theta [..., nk] f32      — block importances
      theta_head [...]         — head importances (normalized per cfg)
      head_kept [...] bool     — early head gate
    """
    if per_query:
        # insert a singleton pooled-q axis per row: the pooled math below
        # then reduces over one query at a time, yielding [..., Sq, nk]
        # (valid carries the Sq axis — _mask_bias always composes q
        # validity in — so the same insertion keeps them aligned)
        int_scores = int_scores[..., :, None, :]
        valid = valid[..., :, None, :]
    theta, bvalid = blocking.pooled_block_theta(int_scores, valid, cfg.block_k)
    return scout_decision(theta, bvalid, valid.sum(axis=(-2, -1)), cfg)


def scout_decision(theta: jnp.ndarray, bvalid: jnp.ndarray,
                   n_valid: jnp.ndarray, cfg: HDPConfig):
    """The decode scout's decisions from pooled page importances.

    ``theta`` [..., nk] are the per-page abs-sums of the integer scores
    over valid positions, ``bvalid`` [..., nk] (broadcastable) the pages
    with any valid position and ``n_valid`` [...] (broadcastable) the
    count of valid positions, which ``normalize_head_score`` divides the
    head importance by. Returns (keep, bvalid, theta, theta_head,
    head_kept) as :func:`decode_scout` documents: the row threshold and
    keep mask, and the early head gate. Every decode scout decides here,
    whichever way its importances were pooled.
    """
    if cfg.block_pruning:
        thr = blocking.row_threshold(theta, cfg.rho_b, bvalid)
        keep = blocking.block_keep_mask(theta, thr, bvalid)
    else:
        keep = jnp.broadcast_to(bvalid, theta.shape)
    theta_head = jnp.where(bvalid, theta, 0.0).sum(-1)
    if cfg.normalize_head_score:
        theta_head = theta_head / jnp.maximum(
            n_valid.astype(jnp.float32), 1.0)
    head_kept = (theta_head > cfg.tau_h) if cfg.head_pruning \
        else jnp.ones_like(theta_head, bool)
    return keep, bvalid, theta, theta_head, head_kept


@dataclasses.dataclass
class HDPStats:
    """Diagnostics emitted by an HDP attention call (all jnp arrays)."""

    keep_blocks: jnp.ndarray      # bool [..., R, C]
    head_kept: jnp.ndarray        # bool [...]
    theta: jnp.ndarray            # [..., R, C] block importances
    theta_head: jnp.ndarray       # [...] head importances (possibly normalized)
    threshold: jnp.ndarray        # [..., R, 1] row thresholds
    block_sparsity: jnp.ndarray   # scalar: pruned-block fraction in kept heads
    head_sparsity: jnp.ndarray    # scalar: pruned-head fraction
    net_sparsity: jnp.ndarray     # scalar: Fig. 10 accounting


def _pad_to_blocks(x: jnp.ndarray, bq: int, axis: int) -> jnp.ndarray:
    l = x.shape[axis]
    pad = (-l) % bq
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _scout_and_mask(iq, ik, cfg: HDPConfig, lq, lk, q_offset, kv_len=None):
    """Integer scout matmul -> block stats -> (keep_blocks, head_kept, aux).

    Returns everything on padded block geometry; caller crops.
    """
    bq, bk = cfg.block_q, cfg.block_k
    integer_atten = jnp.einsum("...qd,...kd->...qk", iq, ik)

    # Valid-entry mask (causal and/or KV length bounded).
    elem_valid = None
    if cfg.causal:
        elem_valid = blocking.causal_element_mask(iq.shape[-2], ik.shape[-2], q_offset)
    if kv_len is not None:
        kmask = (jnp.arange(ik.shape[-2]) < kv_len)[None, :]
        elem_valid = kmask if elem_valid is None else jnp.logical_and(elem_valid, kmask)
    pad_q = iq.shape[-2] - lq
    pad_k = ik.shape[-2] - lk
    if pad_q or pad_k:
        pv = jnp.zeros((iq.shape[-2], ik.shape[-2]), bool)
        pv = pv.at[: lq, : lk].set(True)
        elem_valid = pv if elem_valid is None else jnp.logical_and(elem_valid, pv)

    if elem_valid is not None:
        theta_src = jnp.where(elem_valid, integer_atten, 0.0)
        block_valid = blocking.block_abs_sum(
            elem_valid.astype(integer_atten.dtype), bq, bk) > 0
    else:
        theta_src = integer_atten
        block_valid = None

    theta = blocking.block_abs_sum(theta_src, bq, bk)
    if cfg.block_pruning:
        thresh = blocking.row_threshold(theta, cfg.rho_b, block_valid)
        keep = blocking.block_keep_mask(theta, thresh, block_valid)
    else:
        thresh = jnp.zeros_like(theta[..., :1])
        keep = jnp.ones_like(theta, bool) if block_valid is None else block_valid

    # Head importance: absolute sum over the whole integer map (line 10).
    if block_valid is not None:
        theta_head = jnp.where(block_valid, theta, 0.0).sum(axis=(-2, -1))
        n_valid = (
            elem_valid.astype(jnp.float32).sum()
            if elem_valid is not None
            else jnp.asarray(float(lq * lk))
        )
    else:
        theta_head = theta.sum(axis=(-2, -1))
        n_valid = jnp.asarray(float(lq * lk))
    if cfg.normalize_head_score:
        theta_head = theta_head / jnp.maximum(n_valid, 1.0)
    if cfg.head_pruning:
        head_kept = theta_head > cfg.tau_h  # line 19: proceed iff theta > tau
    else:
        head_kept = jnp.ones_like(theta_head, bool)
    return integer_atten, elem_valid, block_valid, theta, thresh, keep, theta_head, head_kept


def _finish(scores, keep_elem, head_kept, v, cfg: HDPConfig):
    softmax = blocking.approx_softmax if cfg.approx_softmax else blocking.masked_softmax
    prob = softmax(scores, keep_elem)
    out = jnp.einsum("...qk,...kd->...qd", prob, v)
    gate = head_kept[..., None, None].astype(out.dtype)
    return out * gate  # line 33: pruned head -> result = 0


def hdp_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    cfg: HDPConfig,
    *,
    q_offset: int = 0,
    kv_len: Optional[jnp.ndarray] = None,
    return_stats: bool = True,
):
    """Batched HDP attention (fast path) on [..., L, d_h] tensors.

    q_offset: absolute position of q[..., 0, :] (decode); kv_len: optional
    dynamic KV validity bound. Returns (out, HDPStats|None).
    """
    if not cfg.enabled:
        scores = jnp.einsum("...qd,...kd->...qk", q, k) / jnp.sqrt(
            jnp.asarray(q.shape[-1], q.dtype))
        keep = None
        if cfg.causal:
            keep = blocking.causal_element_mask(q.shape[-2], k.shape[-2], q_offset)
        out = jnp.einsum("...qk,...kd->...qd", blocking.masked_softmax(scores, keep), v)
        return out, None

    lq, lk = q.shape[-2], k.shape[-2]
    qp = _pad_to_blocks(q, cfg.block_q, -2)
    kp = _pad_to_blocks(k, cfg.block_k, -2)
    vp = _pad_to_blocks(v, cfg.block_k, -2)

    sq, qq, iq, fq = calibrated_split(qp, cfg)
    sk, kq, ik, fk = calibrated_split(kp, cfg)

    (_, elem_valid, _, theta, thresh, keep, theta_head, head_kept) = _scout_and_mask(
        iq, ik, cfg, lq, lk, q_offset, kv_len)

    # approx = QK^T - FQ.FK^T  (== Integer + Frac1 + Frac2 exactly);
    # 1/(s_q*s_k) maps scores back from the calibrated domain.
    scores = jnp.einsum("...qd,...kd->...qk", qq, kq)
    if cfg.approx:
        scores = scores - jnp.einsum("...qd,...kd->...qk", fq, fk)
    scores = scores / (sq * sk).astype(scores.dtype)
    scores = scores / jnp.sqrt(jnp.asarray(q.shape[-1], scores.dtype))

    keep_elem = blocking.expand_block_mask(keep, cfg.block_q, cfg.block_k)
    if elem_valid is not None:
        keep_elem = jnp.logical_and(keep_elem, elem_valid)

    out = _finish(scores, keep_elem, head_kept, vp, cfg)[..., :lq, :]

    stats = None
    if return_stats:
        block_valid = None
        if elem_valid is not None:
            block_valid = blocking.block_abs_sum(
                elem_valid.astype(jnp.float32), cfg.block_q, cfg.block_k) > 0
        bsp, hsp, net = blocking.net_sparsity(
            keep, head_kept[..., None, None], block_valid)
        stats = HDPStats(keep, head_kept, theta, theta_head, thresh, bsp, hsp, net)
    return out, stats


def hdp_attention_reference(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, cfg: HDPConfig,
    *, q_offset: int = 0,
):
    """Literal Algorithm 2: three-term approximation, explicit mask algebra.

    Slow/materializing; the oracle for tests and paper-fidelity benchmarks.
    """
    lq, lk = q.shape[-2], k.shape[-2]
    qp = _pad_to_blocks(q, cfg.block_q, -2)
    kp = _pad_to_blocks(k, cfg.block_k, -2)
    vp = _pad_to_blocks(v, cfg.block_k, -2)
    sq, _, iq, fq = calibrated_split(qp, cfg)
    sk, _, ik, fk = calibrated_split(kp, cfg)

    (integer_atten, elem_valid, _, theta, thresh, keep, theta_head, head_kept
     ) = _scout_and_mask(iq, ik, cfg, lq, lk, q_offset)

    # Lines 19-28: fractional terms only where Mask == 1 (we compute them
    # densely and mask — numerically identical, since masked entries are
    # excluded from the softmax anyway).
    frac1 = jnp.einsum("...qd,...kd->...qk", iq, fk)
    frac2 = jnp.einsum("...qd,...kd->...qk", fq, ik)
    approximation = integer_atten + frac1 + frac2
    if not cfg.approx:
        approximation = approximation + jnp.einsum("...qd,...kd->...qk", fq, fk)
    approximation = approximation / (sq * sk).astype(approximation.dtype)
    scores = approximation / jnp.sqrt(jnp.asarray(q.shape[-1], approximation.dtype))

    keep_elem = blocking.expand_block_mask(keep, cfg.block_q, cfg.block_k)
    if elem_valid is not None:
        keep_elem = jnp.logical_and(keep_elem, elem_valid)
    out = _finish(scores, keep_elem, head_kept, vp, cfg)[..., :lq, :]
    stats = HDPStats(
        keep, head_kept, theta, theta_head, thresh,
        *blocking.net_sparsity(keep, head_kept[..., None, None], None))
    return out, stats


def dense_attention_reference(q, k, v, *, causal=False, q_offset=0):
    """Exact (unquantized, unpruned) attention — the fidelity yardstick."""
    scores = jnp.einsum("...qd,...kd->...qk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], q.dtype))
    keep = None
    if causal:
        keep = blocking.causal_element_mask(q.shape[-2], k.shape[-2], q_offset)
    prob = blocking.masked_softmax(scores, keep)
    return jnp.einsum("...qk,...kd->...qd", prob, v)
