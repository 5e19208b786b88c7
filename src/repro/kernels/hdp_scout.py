"""HDP integer scout kernel: Integer_Q x Integer_K^T -> block importances,
row-balanced thresholds and keep masks — the paper's PE-array importance
accumulation + Sparsity Engine, fused into one Pallas kernel.

Grid (B*H, nq, nkc): each step multiplies one q tile (the pruning block
row) against a CHUNK of ck KV blocks, pools |scores| per block into a VMEM
theta row; the last chunk computes Theta_i (Alg. 2 line 15) from the full
row and emits the keep mask. Block validity (causal + seq bounds) is
analytic — no data-dependent bookkeeping, matching the ASIC's END_R flag.

The scout reads only integer parts: on TPU these are int8-representable,
so HBM traffic for this pass is ~4x less than the bf16 QK^T it replaces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


F32 = jnp.float32
BIG = 1e30


def _kernel(iq_ref, ik_ref, theta_ref, mask_ref, trow_ref,
            *, rho_b, causal, block_q, block_k, chunk_blocks, nk, nkc,
            sq_true, sk_true):
    i = pl.program_id(1)
    j = pl.program_id(2)
    ck, bk = chunk_blocks, block_k

    # ---- theta for this chunk of blocks (PE-array importance) ----
    iq = iq_ref[0].astype(F32)
    ik = ik_ref[0].astype(F32)
    s = jax.lax.dot_general(iq, ik, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32)  # [bq, ck*bk]
    rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = j * ck * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = (cols < sk_true) & (rows < sq_true)
    if causal:
        valid = valid & (rows >= cols)
    s = jnp.where(valid, jnp.abs(s), 0.0)
    # pool each block's |scores| and place it in the theta row with a
    # lane select: block starts are 128-lane aligned at real widths, and
    # the row is only ever written whole (no dynamic lane-offset store)
    lane = jax.lax.broadcasted_iota(jnp.int32, trow_ref.shape, 1)
    trow = trow_ref[...]                                   # [1, nk_pad]
    for c in range(ck):
        blk = s[:, c * bk:(c + 1) * bk].sum(axis=0, keepdims=True)
        blk = blk.sum(axis=1, keepdims=True)               # [1, 1]
        trow = jnp.where(lane == j * ck + c, blk, trow)
    trow_ref[...] = trow

    # ---- Sparsity Engine: threshold + mask once the row is complete ----
    @pl.when(j == nkc - 1)
    def _finish():
        bidx = lane                                            # block index
        bvalid = (bidx * bk < sk_true) & (bidx < nk)
        if causal:
            bvalid = bvalid & (bidx * bk <= i * block_q + block_q - 1)
        cnt = jnp.maximum(
            bvalid.astype(F32).sum(axis=1, keepdims=True), 1.0)
        tmin = jnp.where(bvalid, trow, BIG).min(axis=1, keepdims=True)
        tmax = jnp.where(bvalid, trow, -BIG).max(axis=1, keepdims=True)
        tmean = jnp.where(bvalid, trow, 0.0).sum(axis=1, keepdims=True) / cnt
        # same f32 arithmetic and row-max clamp as blocking.row_threshold
        rho = jnp.float32(rho_b)
        if rho_b >= 0:
            thr = rho * tmax + (1.0 - rho) * tmean
        else:
            thr = -rho * tmin + (1.0 + rho) * tmean
        thr = jnp.minimum(thr, tmax)
        keep = (trow >= thr) & bvalid
        theta_ref[0, 0] = jnp.where(bvalid, trow, 0.0)
        mask_ref[0, 0] = keep.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("rho_b", "block_q", "block_k",
                                             "causal", "chunk_blocks",
                                             "interpret"))
def hdp_scout(iq, ik, *, rho_b: float, block_q: int = 128,
              block_k: int = 128, causal: bool = True,
              chunk_blocks: int = 8, interpret: bool = False):
    """iq/ik [B,H,S,hd] integer-valued -> (theta, keep, theta_head).

    theta [B,H,nq,nk] f32; keep bool [B,H,nq,nk]; theta_head [B,H].
    """
    B, H, Sq, hd = iq.shape
    Sk = ik.shape[2]
    nq = -(-Sq // block_q)
    nk = -(-Sk // block_k)
    ck = max(1, min(chunk_blocks, nk))
    nkc = -(-nk // ck)
    skp = nkc * ck * block_k
    sqp = nq * block_q
    nk_pad = -(-(nkc * ck) // 128) * 128

    iqp = jnp.pad(iq, ((0, 0), (0, 0), (0, sqp - Sq), (0, 0))
                  ).reshape(B * H, sqp, hd)
    ikp = jnp.pad(ik, ((0, 0), (0, 0), (0, skp - Sk), (0, 0))
                  ).reshape(B * H, skp, hd)

    kernel = functools.partial(
        _kernel, rho_b=rho_b, causal=causal, block_q=block_q,
        block_k=block_k, chunk_blocks=ck, nk=nk, nkc=nkc,
        sq_true=Sq, sk_true=Sk)
    theta, mask = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nkc),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, ck * block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        # one whole [1, nk_pad] row per (head, q tile): the trailing
        # singleton keeps the block's last two dims equal to the array's
        out_specs=[
            pl.BlockSpec((1, 1, 1, nk_pad), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, nk_pad), lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, nq, 1, nk_pad), F32),
            jax.ShapeDtypeStruct((B * H, nq, 1, nk_pad), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, nk_pad), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(iqp, ikp)

    theta = theta[:, :, 0, :nk].reshape(B, H, nq, nk)
    keep = mask[:, :, 0, :nk].reshape(B, H, nq, nk) > 0
    theta_head = theta.sum((-2, -1))
    return theta, keep, theta_head
