"""Block importance, row-balanced thresholds and masks (Alg. 2 lines 6-17).

Everything here operates on arbitrarily-batched score maps [..., Lq, Lk];
block geometry is static. `valid` masks let the same math serve causal LMs
(future blocks are excluded from min/max/mean and never counted as pruned —
a TPU adaptation documented in DESIGN.md; the paper is encoder-only).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

_NEG = -1e30  # used instead of -inf to keep masked softmax NaN-free


def block_abs_sum(scores: jnp.ndarray, block_q: int, block_k: int) -> jnp.ndarray:
    """theta_j = sum |x| over each block  ->  [..., Lq/bq, Lk/bk]."""
    *lead, lq, lk = scores.shape
    if lq % block_q or lk % block_k:
        raise ValueError(f"({lq},{lk}) not divisible by block ({block_q},{block_k})")
    r = scores.reshape(*lead, lq // block_q, block_q, lk // block_k, block_k)
    return jnp.abs(r).sum(axis=(-3, -1))


def block_sum(scores: jnp.ndarray, block_q: int, block_k: int) -> jnp.ndarray:
    """Plain block sum (used by near-zero statistics)."""
    *lead, lq, lk = scores.shape
    r = scores.reshape(*lead, lq // block_q, block_q, lk // block_k, block_k)
    return r.sum(axis=(-3, -1))


def pooled_block_theta(
    scores: jnp.ndarray, valid: jnp.ndarray, block_k: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pool a [..., q, Sk] score slab into ONE row of Sk/block_k blocks.

    The whole q extent is treated as a single block row (decode-shaped
    pooling: with a block-paged KV cache these blocks ARE the cache
    pages). ``valid`` is a positionally-broadcastable bool mask over
    [..., q, Sk]. Returns (theta [..., nk] f32 abs-sum importances,
    bvalid [..., nk] blocks with any valid position).
    """
    s = jnp.where(valid, scores, 0.0)
    *lead, q, sk = s.shape
    theta = jnp.abs(s.reshape(*lead, q, sk // block_k, block_k)).sum(
        axis=(-3, -1))
    *vlead, vq, _ = valid.shape
    bvalid = valid.reshape(*vlead, vq, sk // block_k, block_k).any(
        axis=(-3, -1))
    return theta, bvalid


def row_threshold(
    theta: jnp.ndarray, rho_b, valid: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Theta_i per row of blocks (Alg. 2 line 15), both rho_B branches.

    theta: [..., R, C]; valid: optional bool [..., R, C] marking blocks that
    participate in the statistics. Returns [..., R, 1].

    Both branches are convex combinations bounded by the row max, but in
    floating point they can round one ULP above it — a causal row with a
    single valid block would then prune its only block. The threshold is
    clamped to the row max so the strongest valid block always survives.
    """
    rho = jnp.asarray(rho_b, theta.dtype)
    if valid is None:
        tmin = theta.min(axis=-1, keepdims=True)
        tmax = theta.max(axis=-1, keepdims=True)
        tmean = theta.mean(axis=-1, keepdims=True)
    else:
        big = jnp.asarray(jnp.finfo(theta.dtype).max, theta.dtype)
        tmin = jnp.where(valid, theta, big).min(axis=-1, keepdims=True)
        tmax = jnp.where(valid, theta, -big).max(axis=-1, keepdims=True)
        cnt = valid.sum(axis=-1, keepdims=True).astype(theta.dtype)
        cnt = jnp.maximum(cnt, 1.0)
        tmean = jnp.where(valid, theta, 0.0).sum(axis=-1, keepdims=True) / cnt
    pos = rho * tmax + (1.0 - rho) * tmean
    neg = -rho * tmin + (1.0 + rho) * tmean
    return jnp.minimum(jnp.where(rho >= 0, pos, neg), tmax)


def block_keep_mask(
    theta: jnp.ndarray, threshold: jnp.ndarray, valid: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Mask_i^j = 0 iff theta_j < Theta_i (Alg. 2 line 16); bool keep mask."""
    keep = theta >= threshold
    if valid is not None:
        keep = jnp.logical_and(keep, valid)
    return keep


def expand_block_mask(
    mask: jnp.ndarray, block_q: int, block_k: int
) -> jnp.ndarray:
    """[..., R, C] block mask -> [..., R*bq, C*bk] element mask."""
    m = jnp.repeat(mask, block_q, axis=-2)
    return jnp.repeat(m, block_k, axis=-1)


def causal_block_valid(
    lq: int, lk: int, block_q: int, block_k: int, q_offset: int = 0
) -> jnp.ndarray:
    """Blocks with at least one causally-visible (q >= k) entry.

    q_offset shifts query positions (decode: q_offset = cache_len).
    Returns bool [lq/bq, lk/bk].
    """
    qb = jnp.arange(lq // block_q) * block_q + (block_q - 1) + q_offset  # last q row of block
    kb = jnp.arange(lk // block_k) * block_k  # first k col of block
    return qb[:, None] >= kb[None, :]


def causal_element_mask(lq: int, lk: int, q_offset: int = 0) -> jnp.ndarray:
    q = jnp.arange(lq) + q_offset
    k = jnp.arange(lk)
    return q[:, None] >= k[None, :]


def apply_score_mask(scores: jnp.ndarray, keep: jnp.ndarray) -> jnp.ndarray:
    """Exclusion semantics: pruned entries leave the softmax entirely."""
    return jnp.where(keep, scores, jnp.asarray(_NEG, scores.dtype))


def masked_softmax(scores: jnp.ndarray, keep: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Row softmax with exclusion; fully-pruned rows produce zeros."""
    if keep is not None:
        scores = apply_score_mask(scores, keep)
    m = scores.max(axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    if keep is not None:
        e = jnp.where(keep, e, 0.0)
    s = e.sum(axis=-1, keepdims=True)
    return e / jnp.maximum(s, jnp.asarray(1e-30, scores.dtype))


# ---------------------------------------------------------------------------
# ASIC-faithful polynomial softmax (paper Sec. IV-E): 2nd-order polynomial
# exponent with range reduction + linear-approximation reciprocal.
# ---------------------------------------------------------------------------

_LN2 = 0.6931471805599453


def poly_exp(x: jnp.ndarray) -> jnp.ndarray:
    """I-BERT-style 2nd-order polynomial exp for x <= 0.

    e^x = 2^(-z) * e^r with r in (-ln2, 0];  e^r ~ 0.3585 (r+1.353)^2 + 0.344.
    """
    x = jnp.minimum(x, 0.0)
    z = jnp.floor(-x / _LN2)
    r = x + z * _LN2
    p = 0.3585 * (r + 1.353) ** 2 + 0.344
    return p * jnp.exp2(-z)


def linear_reciprocal(s: jnp.ndarray, newton_iters: int = 2) -> jnp.ndarray:
    """Reciprocal via linear approximation on the mantissa + Newton steps.

    For s = m * 2^e with m in [1, 2): 1/m ~ 24/17 - 8/17*m (the classical
    Newton-Raphson division seed rescaled to [1,2)), refined by Newton
    iterations y <- y * (2 - s*y) — matching a cheap fixed-point divider.
    """
    s = jnp.maximum(s, 1e-30)
    e = jnp.floor(jnp.log2(s))
    m = s * jnp.exp2(-e)
    y = (24.0 / 17.0 - 8.0 / 17.0 * m) * jnp.exp2(-e)
    for _ in range(newton_iters):
        y = y * (2.0 - s * y)
    return y


def approx_softmax(scores: jnp.ndarray, keep: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Softmax as the HDP softmax unit computes it (poly exp + lin recip)."""
    if keep is not None:
        scores = apply_score_mask(scores, keep)
    m = scores.max(axis=-1, keepdims=True)
    e = poly_exp(scores - m)
    if keep is not None:
        e = jnp.where(keep, e, 0.0)
    s = e.sum(axis=-1, keepdims=True)
    return e * linear_reciprocal(s)


def net_sparsity(
    keep_blocks: jnp.ndarray,
    head_kept: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(block_sparsity_in_kept_heads, head_sparsity, net_sparsity).

    Net sparsity counts a block as skipped if its head was pruned OR the
    block itself was pruned — the paper's Fig. 10 accounting. All fractions
    are over *valid* (causally reachable) blocks.
    """
    kb = keep_blocks.astype(jnp.float32)
    hk = head_kept.astype(jnp.float32)  # [..., 1, 1]-broadcastable
    if valid is None:
        valid_f = jnp.ones_like(kb)
    else:
        valid_f = valid.astype(jnp.float32) * jnp.ones_like(kb)
    total = jnp.maximum(valid_f.sum(), 1.0)
    kept_and_head = kb * hk * valid_f
    block_pruned = (valid_f - kb * valid_f) * hk
    head_pruned = valid_f * (1.0 - hk)
    block_sp = block_pruned.sum() / jnp.maximum((valid_f * hk).sum(), 1.0)
    head_sp = head_pruned.sum() / total
    net = 1.0 - kept_and_head.sum() / total
    return block_sp, head_sp, net
