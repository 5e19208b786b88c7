"""Block-sparse FUM attention kernel — the TPU analogue of the paper's
Fetch-Upon-Mask dataflow.

Scalar-prefetched per-(head, q-block) lists of surviving KV block indices
drive the K/V BlockSpec index_maps, so pruned blocks are NEVER DMA'd from
HBM — the memory-access saving the HDP co-processor gets from its mask
registers. Scores on surviving blocks use the paper's approximation
QK^T - FQ FK^T (fractional parts recomputed on the VPU via trunc, costing
no extra HBM traffic). Early-pruned heads skip all compute via a
prefetched head gate.

The grid is (B*H, nq, max_keep) — static shape, so rows keeping more than
max_keep blocks drop their lowest-importance extras (quantified in
benchmarks; exact when max_keep = nk).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


F32 = jnp.float32
NEG = -1e30


def _kernel(idx_ref, cnt_ref, head_ref, sscale_ref, len_ref,  # scalar prefetch
            q_ref, k_ref, v_ref, o_ref,            # tensors
            acc_ref, m_ref, l_ref,                 # scratch
            *, scale, causal, approx, block_q, block_k, max_keep):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    active = (j < cnt_ref[b, i]) & (head_ref[b] > 0)

    @pl.when(active)
    def _body():
        q = q_ref[0].astype(F32)
        k = k_ref[0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
        if approx:
            fq = q - jnp.trunc(q)
            fk = k - jnp.trunc(k)
            s = s - jax.lax.dot_general(fq, fk, (((1,), (1,)), ((), ())),
                                        preferred_element_type=F32)
        # static 1/sqrt(hd) plus the dynamic calibration rescale 1/(s_q s_k)
        s = s * (scale * sscale_ref[0])
        kv_blk = idx_ref[b, i, j]
        rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = kv_blk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        valid = cols < len_ref[b]
        if causal:
            valid = valid & (rows >= cols)
        s = jnp.where(valid, s, NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=F32)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(j == max_keep - 1)
    def _fin():
        l = jnp.maximum(l_ref[...], 1e-30)
        out = acc_ref[...] / l
        gate = (head_ref[b] > 0).astype(F32)   # pruned head -> zeros
        o_ref[0] = (out * gate).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "approx", "block_q", "block_k", "interpret"))
def hdp_block_sparse_attention(q, k, v, kv_idx, counts, head_kept, *,
                               causal: bool = True, approx: bool = True,
                               block_q: int = 128, block_k: int = 128,
                               score_scale=None, kv_len=None,
                               interpret: bool = False):
    """q,k,v [B,H,S,hd]; kv_idx [B,H,nq,max_keep] int32; counts [B,H,nq];
    head_kept [B,H] (bool/int); score_scale: optional calibration rescale
    1/(s_q*s_k) applied to scores; kv_len [B,H] optional per-row valid KV
    length (serving decode: cache positions beyond the current token are
    masked — defaults to the full Sk). Returns [B,H,Sq,hd]."""
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    sqp = -(-Sq // block_q) * block_q
    skp = -(-Sk // block_k) * block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - Sq), (0, 0))
                 ).reshape(B * H, sqp, hd)
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skp - Sk), (0, 0))
                 ).reshape(B * H, skp, hd)
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skp - Sk), (0, 0))
                 ).reshape(B * H, skp, hd)
    nq = sqp // block_q
    max_keep = kv_idx.shape[-1]
    idx = kv_idx.reshape(B * H, nq, max_keep).astype(jnp.int32)
    cnt = counts.reshape(B * H, nq).astype(jnp.int32)
    hk = head_kept.reshape(B * H).astype(jnp.int32)
    ss = jnp.asarray(1.0 if score_scale is None else score_scale,
                     F32).reshape(1)
    lens = (jnp.full((B * H,), Sk, jnp.int32) if kv_len is None
            else kv_len.reshape(B * H).astype(jnp.int32))

    kernel = functools.partial(
        _kernel, scale=1.0 / (hd ** 0.5), causal=causal, approx=approx,
        block_q=block_q, block_k=block_k, max_keep=max_keep)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B * H, nq, max_keep),
        in_specs=[
            pl.BlockSpec((1, block_q, hd),
                         lambda b, i, j, idx, c, h, s, le: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, i, j, idx, c, h, s, le: (b, idx[b, i, j], 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, i, j, idx, c, h, s, le: (b, idx[b, i, j], 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda b, i, j, idx, c, h, s, le: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), F32),
            pltpu.VMEM((block_q, 1), F32),
            pltpu.VMEM((block_q, 1), F32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, sqp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(idx, cnt, hk, ss, lens, qp, kp, vp)
    return out.reshape(B, H, sqp, hd)[:, :, :Sq]
