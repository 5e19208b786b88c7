"""Dense flash attention Pallas TPU kernel (the paper's dense baseline).

Grid (B*H, nq, nk), innermost kv dim sequential with online-softmax
accumulators in VMEM scratch — the canonical TPU tiling: q block stays
resident, K/V blocks stream HBM->VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


F32 = jnp.float32
NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, scale, causal, block_q, block_k, nk, sq, sk):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip blocks strictly in the future of the whole q tile
    run = (not causal) or (j * block_k <= i * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(F32)
        k = k_ref[0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = cols < sk
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

    @pl.when(j == nk - 1)
    def _fin():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q,k,v [B,H,S,hd] -> [B,H,S,hd]."""
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    sqp = -(-Sq // block_q) * block_q
    skp = -(-Sk // block_k) * block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - Sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skp - Sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skp - Sk), (0, 0)))
    qr = qp.reshape(B * H, sqp, hd)
    kr = kp.reshape(B * H, skp, hd)
    vr = vp.reshape(B * H, skp, hd)
    nq, nk = sqp // block_q, skp // block_k

    kernel = functools.partial(
        _kernel, scale=1.0 / (hd ** 0.5), causal=causal, block_q=block_q,
        block_k=block_k, nk=nk, sq=Sq, sk=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, sqp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), F32),
            pltpu.VMEM((block_q, 1), F32),
            pltpu.VMEM((block_q, 1), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, sqp, hd)[:, :, :Sq]
