"""Gather-free paged FUM decode kernel — page-table-native Fetch-Upon-Mask.

The block-sparse kernel in ``hdp_block_attn`` consumes contiguous K/V, so
the paged serving path had to gather surviving pages into a dense slab
first — O(B*Sk) memory traffic regardless of how many pages the scout
pruned. This kernel removes the gather entirely: the *page pool* is the
kernel input, and scalar-prefetched per-row lists of surviving pool page
ids drive the K/V BlockSpec index maps. A pruned page's id never appears
in the list, so its HBM is never DMA'd — the paper's co-processor
dataflow, now honored at the memory system level for serving decode.

Grid is (B, N, max_keep): one batch row x kv head per program, streaming
that row's kept pages in ascending logical order (monotone DMA). The G
query heads of a GQA group AND the Sq query rows of a multi-query verify
call ride in the block's sublane dim and share the page stream — a
speculative-verify round reads each surviving page ONCE for all Sq rows
instead of once per token, which is the round's bandwidth win. Per-row
keep masks and KV extents still apply inside the softmax: verify rows
sit at consecutive positions, so row ``r``'s valid extent is the base
``kv_len`` plus its query index (``r % Sq``) — no extra prefetch array.

Two pool formats:

* fp32 pool — K arrives full-precision and is snapped to the fixed-point
  grid on the VPU (trunc/round cost no extra HBM traffic), matching the
  write-time-quantized semantics of the XLA stage exactly.
* int8 pool (``k_scale``/``v_scale`` passed) — pages arrive as int8
  codes (4x less DMA per surviving page) and are dequantized IN REGISTER
  from scalar-prefetched per-page scales; the decoded values land
  exactly on the fixed-point grid, so no re-snap is needed and the
  scores match the XLA dequant path bit for bit (power-of-two scales
  commute exactly with the dots). The -128 poison sentinel decodes to
  NaN (tripwire), and a NaN page scale poisons the whole page.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.quant import POISON_CODE, int_frac_split, quantize_fixed

F32 = jnp.float32
NEG = -1e30


def _kernel(pid_ref, logical_ref, cnt_ref, len_ref,   # scalar prefetch
            q_ref, k_ref, v_ref, keep_ref, o_ref,     # tensors
            acc_ref, m_ref, l_ref,                    # scratch
            *, scale, approx, int_bits, frac_bits, ps, max_keep, n_q,
            kscl_ref=None, vscl_ref=None):
    b = pl.program_id(0)
    n = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < cnt_ref[b])
    def _body():
        q = q_ref[0, 0].astype(F32)                   # [G*Sq, hd] fixed grid
        if kscl_ref is None:
            # fp32 pool: snap the full-precision page to the write-time
            # scout's grid on the VPU (the shared core.quant ops are
            # plain jnp — safe here)
            k = k_ref[0, 0, 0].astype(F32)            # [ps, hd] pool page
            kq = quantize_fixed(k, int_bits, frac_bits)
            v = v_ref[0, 0, 0]
        else:
            # int8 pool: dequantize in register from the prefetched
            # per-page scale — decoded values already sit on the grid
            kc = k_ref[0, 0, 0].astype(jnp.int32)     # [ps, hd] int8 codes
            ks = kscl_ref[pid_ref[b, j], n]
            kq = jnp.where(kc == POISON_CODE, jnp.nan, kc.astype(F32)) * ks
            v = v_ref[0, 0, 0].astype(F32) * vscl_ref[pid_ref[b, j], n]
        s = jax.lax.dot_general(q, kq, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
        if approx:
            fq = int_frac_split(q)[1]
            fk = int_frac_split(kq)[1]
            s = s - jax.lax.dot_general(fq, fk, (((1,), (1,)), ((), ())),
                                        preferred_element_type=F32)
        s = s * scale
        cols = logical_ref[b, j] * ps + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        # per-row KV extent: verify rows are consecutive positions, so
        # row r (query index r % Sq) extends the base length by r % Sq
        sq_idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % n_q
        valid = cols < (len_ref[b] + sq_idx)
        valid = valid & (keep_ref[0, 0, 0] > 0)      # [G*Sq, 1] row keep
        s = jnp.where(valid, s, NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=F32)
        acc_ref[...] = acc_ref[...] * corr + pv

    @pl.when(j == max_keep - 1)
    def _fin():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "approx", "int_bits", "frac_bits", "interpret"))
def hdp_paged_fum_decode(qq, k_pool, v_pool, page_ids, logical, counts,
                         keep, kv_len, *, approx: bool = True,
                         int_bits: int = 4, frac_bits: int = 12,
                         k_scale=None, v_scale=None, layer=None,
                         interpret: bool = False):
    """qq [B,N,G,Sq,hd] fixed-grid queries (Sq = 1 for plain decode, > 1
    for the speculative multi-query verify); k/v_pool [P,N,ps,hd]
    head-major page pools, or the layer-stacked [L,P,N,ps,hd] pools with
    ``layer`` the int32 layer to read (the pool then reaches the kernel
    as the serving loop carries it — no per-layer slice is copied);
    page_ids/logical [B,mk] int32 (pool id / slot position of each kept
    page — the union over query rows, scratch-0-padded past counts);
    counts [B] int32 kept pages per row; keep [B,mk,N,G,Sq] int32
    per-query-row keep; kv_len [B] int32 valid KV extent of query row 0
    (row j's extent is kv_len + j: verify rows are consecutive
    positions). ``k_scale``/``v_scale`` [P,N] fp32 (this layer's) mark a
    quantized pool (int8 codes + per-page scales, dequantized in register
    from scalar prefetch). Returns [B,N,G,Sq,hd] (head gate applied by
    the caller). Pages absent from ``page_ids`` are never read.
    """
    B, N, G, Sq, hd = qq.shape
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    ps = k_pool.shape[3]
    mk = page_ids.shape[1]
    rows = G * Sq
    quantized = k_scale is not None
    base = functools.partial(
        _kernel, scale=1.0 / (hd ** 0.5), approx=approx, int_bits=int_bits,
        frac_bits=frac_bits, ps=ps, max_keep=mk, n_q=Sq)

    # scalar-prefetch operands: the page lists and the layer index driving
    # the BlockSpec index maps, plus (quantized pools) the per-page scales
    # the kernel body reads at dequant time. Prefetch refs arrive
    # positionally ahead of the tensor refs, so the wrapper drops the
    # layer ref (only the index maps read it) and peels the two scale refs
    # off into the keyword slots; the index-map lambdas take one ref per
    # prefetch operand after the grid indices.
    if quantized:
        n_pref = 7

        def kernel(pid, lg, c, le, lay, ks, vs, *refs):
            return base(pid, lg, c, le, *refs, kscl_ref=ks, vscl_ref=vs)

        def imap(fn):
            return lambda b, n, j, pid, lg, c, le, lay, ks, vs: fn(
                b, n, j, pid, lay)
    else:
        n_pref = 5

        def kernel(pid, lg, c, le, lay, *refs):
            return base(pid, lg, c, le, *refs)

        def imap(fn):
            return lambda b, n, j, pid, lg, c, le, lay: fn(b, n, j, pid, lay)

    # TPU blocks need their last two dims (8, 128)-aligned or whole, so
    # every operand keeps a whole trailing tile: the G*Sq query rows of a
    # head share one [rows, hd] tile, and the head-major pool's [ps, hd]
    # page of one head is itself a whole tile — the pool reaches the
    # kernel as stored (no relayout copy), and the DMA moves exactly that
    # head's slice of each surviving page.
    page = pl.BlockSpec(
        (1, 1, 1, ps, hd),
        imap(lambda b, n, j, pid, lay: (lay[0], pid[b, j], n, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pref,
        grid=(B, N, mk),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd),
                         imap(lambda b, n, j, pid, lay: (b, n, 0, 0))),
            page,
            page,
            pl.BlockSpec((1, 1, 1, rows, 1),
                         imap(lambda b, n, j, pid, lay: (b, j, n, 0, 0))),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               imap(lambda b, n, j, pid, lay: (b, n, 0, 0))),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), F32),
            pltpu.VMEM((rows, 1), F32),
            pltpu.VMEM((rows, 1), F32),
        ],
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N, rows, hd), qq.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    pref = (page_ids, logical, counts, kv_len,
            jnp.asarray(layer, jnp.int32).reshape(1))
    if quantized:
        pref += (k_scale.astype(F32), v_scale.astype(F32))
    out = call(*pref, qq.reshape(B, N, rows, hd), k_pool, v_pool,
               keep.reshape(B, mk, N, rows, 1))
    return out.reshape(B, N, G, Sq, hd)
