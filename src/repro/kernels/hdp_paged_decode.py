"""Gather-free paged FUM decode kernel — page-table-native Fetch-Upon-Mask.

The block-sparse kernel in ``hdp_block_attn`` consumes contiguous K/V, so
the paged serving path had to gather surviving pages into a dense slab
first — O(B*Sk) memory traffic regardless of how many pages the scout
pruned. This kernel removes the gather entirely: the *page pool* stays in
HBM (``pl.ANY``) and the kernel DMAs, page by page, only the pool pages
named in each slot's kept-page list. A pruned page's id never appears in
the list, so its HBM is never read — the paper's co-processor dataflow,
honored at the memory system level for serving decode.

The grid is one step per slot. A step walks ``ceil(counts[b] / ppb)``
compute blocks of ``ppb`` kept pages (a dynamic trip count), so table
columns past the kept count cost nothing: no grid step, no DMA. One DMA
per kept page and pool brings the page of every kv head at once (a
``[N, ps, hd]`` pool page is contiguous), into one half of a
double-buffered VMEM block: block i+1 — or, after a slot's last block,
the next live slot's first block — is in flight while block i computes.

Each block's dots are wide: per kv head, the G query heads of a GQA group
AND the Sq query rows of a multi-query verify call ride in the sublane
dim (``[G*Sq, hd]``) against the block's ``[ppb*ps, hd]`` keys — a
speculative-verify round reads each surviving page ONCE for all Sq rows.
Per-head, per-row keep masks and KV extents still apply inside the
softmax: verify rows sit at consecutive positions, so row ``r``'s valid
extent is the base ``kv_len`` plus its query index (``r % Sq``).

Two pool formats:

* fp32 / bf16 pool — K arrives full-precision and is snapped to the
  fixed-point grid on the VPU (trunc/round cost no extra HBM traffic),
  matching the write-time-quantized semantics of the XLA stage exactly.
* int8 pool (``k_scale``/``v_scale`` passed) — pages arrive as int8
  codes (4x less DMA per surviving page) and are dequantized IN REGISTER
  from scalar-prefetched per-page scales; the decoded values land
  exactly on the fixed-point grid, so no re-snap is needed and the
  scores match the XLA dequant path bit for bit (power-of-two scales
  commute exactly with the dots). V is never scaled element by element:
  each page's V scale multiplies its columns of the softmax weights
  before the PV dot, which for a power-of-two scale gives the same bits.
  The -128 poison sentinel of K decodes to NaN (tripwire), and a NaN
  page scale poisons every score (K) or output (V) that reads the page.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.quant import POISON_CODE, int_frac_split, quantize_fixed

F32 = jnp.float32
NEG = -1e30

#: VMEM one compute block may take: the double-buffered K and V pages of
#: every kv head plus the float32 tiles the block's dots read, per head
#: (decoded K, its fraction, decoded V — about four [ps, hd] f32 tiles
#: per page). Half of v5e's default 16 MiB scoped VMEM.
BLOCK_VMEM_BYTES = 8 << 20


def pages_per_block(table_pages: int, k_pool, v_pool) -> int:
    """Kept pages one compute block covers (``ppb``), from shapes alone:
    the table width and the pools' page shape and dtypes.

    A block pays a fixed cost (its DMA issue and waits, the softmax
    bookkeeping, the dots' fill) and a cost per page it computes, and a
    row's last block pays for ``ppb`` pages whatever it holds (on a TPU
    v5e about 0.54 us a block against 0.09 us a page computed, for pages
    of 2 kv heads x 128 x 128 int8). With ``sqrt(table_pages)`` pages a
    block, a row as wide as the table visits as many blocks as its last
    block can waste pages. The VMEM of a double-buffered block caps it."""
    *_, n_kv, page_size, head_dim = k_pool.shape
    itemsize = max(k_pool.dtype.itemsize, v_pool.dtype.itemsize)
    page = n_kv * page_size * head_dim
    per_page = 2 * 2 * page * itemsize + 4 * page_size * head_dim * 4
    cap = max(1, BLOCK_VMEM_BYTES // per_page)
    return max(1, min(table_pages, cap, math.isqrt(table_pages)))


def _kernel(pid_ref, lg_ref, cnt_ref, len_ref, nxt_ref, lay_ref, *refs,
            scale, approx, int_bits, frac_bits, ppb, mk, n_q, quantized):
    if quantized:
        ks_ref, vs_ref, *refs = refs
    (q_ref, keep_ref, k_hbm, v_hbm, o_ref,
     kbuf, vbuf, sem, acc_ref, m_ref, l_ref, cur_ref) = refs
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    _, N, S, hd = kbuf.shape               # S = ppb * ps
    ps = S // ppb
    rows = q_ref.shape[2]
    lay = lay_ref[0]

    def dma(slot, blk, buf, wait):
        """Start (or wait for) the copies of block ``blk`` of ``slot``:
        one per kept page and pool, every kv head at once; columns past
        the slot's kept count are not copied."""
        cnt = cnt_ref[slot]
        for p in range(ppb):
            c = blk * ppb + p

            @pl.when(c < cnt)
            def _():
                pid = pid_ref[slot * mk + c]
                for i, (src, dst) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                    cp = pltpu.make_async_copy(
                        src.at[lay, pid], dst.at[buf, :, pl.ds(p * ps, ps)],
                        sem.at[i, buf])
                    if wait:
                        cp.wait()
                    else:
                        cp.start()

    @pl.when(b == 0)
    def _first():
        # every later slot's first block is started by the live slot
        # before it; the first live slot's is started here
        cur_ref[0] = 0
        first = nxt_ref[0]

        @pl.when(first < n_slots)
        def _():
            dma(first, 0, 0, wait=False)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    cnt = cnt_ref[b]
    n_blk = (cnt + ppb - 1) // ppb
    # per-row KV extent: verify rows are consecutive positions, so row r
    # (query index r % Sq) extends the base length by r % Sq
    row_len = len_ref[b] + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) % n_q
    in_page = jax.lax.broadcasted_iota(jnp.int32, (rows, S), 1) % ps
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, mk), 1)

    def compute(blk, buf):
        cols = [blk * ppb + p for p in range(ppb)]
        live = [c < cnt for c in cols]
        idx = [b * mk + jnp.minimum(c, mk - 1) for c in cols]
        start = [lg_ref[i] * ps for i in idx]
        for n in range(N):
            q = q_ref[0, n].astype(F32)                # [rows, hd] fixed grid
            kc = kbuf[buf, n]                          # [S, hd] ppb pages
            vc = vbuf[buf, n]
            if quantized:
                # int8 pool: dequantize in register from the prefetched
                # per-page scales — decoded values already sit on the grid
                ks = jnp.concatenate(
                    [jnp.full((ps, 1), ks_ref[pid_ref[i] * N + n], F32)
                     for i in idx], axis=0)            # [S, 1]
                kf = kc.astype(F32)
                kq = jnp.where(kf == POISON_CODE, jnp.nan, kf) * ks
                # V's scale goes on the weights' columns (below); a column
                # past the kept count weighs 0, whatever its stale buffer
                vs = jnp.concatenate(
                    [jnp.full((1, ps), jnp.where(
                        lv, vs_ref[pid_ref[i] * N + n], 0.0), F32)
                     for lv, i in zip(live, idx)], axis=1)   # [1, S]
                v = vc.astype(F32)
            else:
                # fp32 / bf16 pool: snap the full-precision page to the
                # write-time scout's grid on the VPU
                kq = quantize_fixed(kc.astype(F32), int_bits, frac_bits)
                v = vc
            if vc.dtype != jnp.int8:
                # a float buffer past the kept count holds stale pages
                # (NaN for a poisoned one): zero those rows for the PV dot
                n_live = (cnt - blk * ppb) * ps
                v = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0)
                              < n_live, v.astype(F32), 0.0).astype(v.dtype)
            s = jax.lax.dot_general(q, kq, (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32)
            if approx:
                fq = int_frac_split(q)[1]
                fk = int_frac_split(kq)[1]
                s = s - jax.lax.dot_general(fq, fk, (((1,), (1,)), ((), ())),
                                            preferred_element_type=F32)
            s = s * scale
            # each page's valid offsets: below the row's extent, on a kept
            # page the row keeps (per head, per query row)
            keep_n = keep_ref[0, n]                    # [rows, mk]
            lim = []
            for c, lv, st in zip(cols, live, start):
                kept = jnp.max(jnp.where(col == c, keep_n, 0.0), axis=1,
                               keepdims=True)          # [rows, 1]
                lim.append(jnp.broadcast_to(
                    jnp.where(lv & (kept > 0), row_len - st, 0), (rows, ps)))
            valid = in_page < jnp.concatenate(lim, axis=1)
            s = jnp.where(valid, s, NEG)

            m_prev = m_ref[n]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            p = jnp.where(valid, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[n] = l_ref[n] * corr + p.sum(-1, keepdims=True)
            m_ref[n] = m_new
            if quantized:
                p = p * vs
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=F32)
            acc_ref[n] = acc_ref[n] * corr + pv

    def block(blk, buf):
        nxt = 1 - buf

        @pl.when(blk + 1 < n_blk)
        def _():
            dma(b, blk + 1, nxt, wait=False)

        @pl.when(blk + 1 == n_blk)
        def _():
            # the slot's last block: start the next live slot's first
            nb = nxt_ref[b + 1]

            @pl.when(nb < n_slots)
            def _():
                dma(nb, 0, nxt, wait=False)

        dma(b, blk, buf, wait=True)
        compute(blk, buf)
        return nxt

    cur_ref[0] = jax.lax.fori_loop(0, n_blk, block, cur_ref[0])
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_fum(qq, k_pool, v_pool, page_ids, logical, counts, keep, kv_len,
               layer, k_scale, v_scale, *, ppb, approx, int_bits, frac_bits,
               interpret):
    """The kernel call for a given block size ``ppb`` (stacked pools;
    ``hdp_paged_fum_decode`` derives ``ppb`` and documents the rest)."""
    B, N, G, Sq, hd = qq.shape
    ps = k_pool.shape[3]
    mk = page_ids.shape[1]
    rows = G * Sq
    quantized = k_scale is not None
    kernel = functools.partial(
        _kernel, scale=1.0 / (hd ** 0.5), approx=approx, int_bits=int_bits,
        frac_bits=frac_bits, ppb=ppb, mk=mk, n_q=Sq, quantized=quantized)
    # nxt[j]: the first slot at or after j with a kept page (B if none),
    # so a slot's last block can start the next live slot's first
    live = jnp.where(counts > 0, jnp.arange(B, dtype=jnp.int32), B)
    nxt = jnp.append(jax.lax.cummin(live, reverse=True), B).astype(jnp.int32)
    # scalar prefetch (SMEM): the page lists, flat — a 2-D SMEM array pads
    # its last dim to 128 words — and the layer index; quantized pools add
    # their flat per-page scales
    pref = (page_ids.reshape(-1), logical.reshape(-1), counts, kv_len, nxt,
            jnp.asarray(layer, jnp.int32).reshape(1))
    if quantized:
        pref += (k_scale.astype(F32).reshape(-1),
                 v_scale.astype(F32).reshape(-1))
    slot = pl.BlockSpec((1, N, rows, hd), lambda b, *_: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(pref),
        grid=(B,),
        in_specs=[
            slot,
            pl.BlockSpec((1, N, rows, mk), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=slot,
        scratch_shapes=[
            pltpu.VMEM((2, N, ppb * ps, hd), k_pool.dtype),
            pltpu.VMEM((2, N, ppb * ps, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((N, rows, hd), F32),
            pltpu.VMEM((N, rows, 1), F32),
            pltpu.VMEM((N, rows, 1), F32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N, rows, hd), qq.dtype),
        # one slot's last block prefetches the next slot's first: the
        # slots run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hdp_paged_fum_decode",
    )
    out = call(*pref, qq.reshape(B, N, rows, hd),
               keep.reshape(B, N, rows, mk).astype(F32), k_pool, v_pool)
    return out.reshape(B, N, G, Sq, hd)


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
    page — the union over heads and query rows, ascending, 0-padded past
    counts); counts [B] int32 kept pages per row; keep [B,N,G,Sq,mk]
    per-head, per-query-row keep of each listed page; kv_len [B] int32
    valid KV extent of query row 0 (row j's extent is kv_len + j: verify
    rows are consecutive positions). ``k_scale``/``v_scale`` [P,N] fp32
    (this layer's) mark a quantized pool (int8 codes + per-page scales,
    dequantized in register from scalar prefetch). Returns [B,N,G,Sq,hd]
    (head gate applied by the caller). Pages absent from ``page_ids``, and
    listed columns past ``counts``, are never read. The compute block
    size comes from the shapes (``pages_per_block``).
    """
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    ppb = pages_per_block(page_ids.shape[1], k_pool, v_pool)
    return _paged_fum(qq, k_pool, v_pool, page_ids, logical, counts, keep,
                      kv_len, layer, k_scale, v_scale, ppb=ppb, approx=approx,
                      int_bits=int_bits, frac_bits=frac_bits,
                      interpret=interpret)
