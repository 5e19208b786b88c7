"""Paged integer scout kernel — stage 1 of HDP decode over an int8 page pool.

The decode scout has to read the integer part of every cached key of a
slot, pruned or not: its page importances decide which pages stage 3
fetches. Over an int8 pool those integer parts are a view of the stored
codes (``core.quant.pool_view_finite`` then ``trunc``). Gathering every
table column of every slot out of the pool, widening the codes to
float32 and multiplying the whole ``[.., nP * ps]`` score slab costs
several times the bytes the scout needs, and counts the columns that
are not allocated yet.

This kernel reads each slot's int8 K codes once, straight from the
(layer-stacked) pool in HBM (``pl.ANY``), and returns the page
importances ``theta`` instead of a score slab. The grid is one step per
slot. A step walks ``ceil(count / ppb)`` blocks of ``ppb`` table columns
(a dynamic trip count), where ``count = ceil(kv_len / ps)`` are the
columns that hold any valid position: columns past it, and slots with
nothing cached, cost no DMA. One DMA per page brings the page of every
kv head at once (an ``[N, ps, hd]`` pool page is contiguous), into one
half of a double-buffered VMEM block: the next block — or, after a
slot's last block, the next live slot's first — is in flight while a
block computes.

In registers each code becomes its integer part exactly as the XLA
scout derives it: the poison code -128 reads 0, the rest are scaled by
the static grid step ``pool_scale(int_bits)`` and truncated toward zero.
Per kv head the block's dot ``iq [G, hd] x ik [ppb * ps, hd]^T`` runs in
bfloat16 with float32 accumulation. The integer parts of queries and
keys are at most 16 in magnitude, so they are exact in bfloat16; a score
is an integer of magnitude at most ``hd * 16 * 16`` and a page's abs-sum
at most ``ps`` times that (4.2 M at hd = ps = 128, below 2^24), so
``theta`` is an exact integer in float32 whatever the order of the
sums, and equals the XLA scout's bit for bit. Positions at or past
``kv_len`` are masked before the abs-sum; each page's sum is placed in a
lane-dense ``[G, nP_pad]`` row by a lane select, and the row is written
whole (Mosaic refuses a dynamic lane-offset store).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.quant import POISON_CODE, pool_scale

F32 = jnp.float32

#: VMEM one compute block may take: the double-buffered int8 pages of
#: every kv head plus one head's widened codes (float32 and bfloat16).
#: Half of v5e's default 16 MiB scoped VMEM.
BLOCK_VMEM_BYTES = 8 << 20


def scout_pages_per_block(table_pages: int, k_pool) -> int:
    """Table columns one scout block covers (``ppb``), from shapes alone:
    the table width and the K pool's page shape and dtype.

    A block pays a fixed cost (its DMA issue and waits, the loop) and a
    cost per column it computes, and a slot's last block computes all
    ``ppb`` columns whatever it holds (only its live columns are DMA'd).
    With ``sqrt(table_pages)`` columns a block, a slot as wide as the
    table visits as many blocks as its last block can waste columns. The
    VMEM of a double-buffered block caps it."""
    *_, n_kv, page_size, head_dim = k_pool.shape
    page = n_kv * page_size * head_dim * k_pool.dtype.itemsize
    per_page = 2 * page + page_size * head_dim * (4 + 2)
    cap = max(1, BLOCK_VMEM_BYTES // per_page)
    return max(1, min(table_pages, cap, math.isqrt(table_pages)))


def _kernel(pid_ref, cnt_ref, len_ref, nxt_ref, lay_ref, iq_ref, k_hbm,
            theta_ref, kbuf, sem, cur_ref, *, ppb, n_cols, step):
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    _, N, S, _ = kbuf.shape                # S = ppb * ps
    ps = S // ppb
    G = iq_ref.shape[2]
    lay = lay_ref[0]

    def dma(slot, blk, buf, wait):
        """Start (or wait for) the copies of block ``blk`` of ``slot``:
        one per live column, every kv head at once."""
        cnt = cnt_ref[slot]
        for p in range(ppb):
            c = blk * ppb + p

            @pl.when(c < cnt)
            def _():
                cp = pltpu.make_async_copy(
                    k_hbm.at[lay, pid_ref[slot * n_cols + c]],
                    kbuf.at[buf, :, pl.ds(p * ps, ps)], sem.at[buf])
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

    theta_ref[...] = jnp.zeros_like(theta_ref)
    cnt = cnt_ref[b]
    n_blk = (cnt + ppb - 1) // ppb
    kv_len = len_ref[b]
    lane = jax.lax.broadcasted_iota(jnp.int32, theta_ref.shape[2:], 1)
    pos = jax.lax.broadcasted_iota(jnp.int32, (G, S), 1)

    def compute(blk, buf):
        # the block's valid positions; a column past the count holds a
        # stale (finite) page, masked here like the tail of the last one
        live = pos < kv_len - blk * S
        for n in range(N):
            kf = kbuf[buf, n].astype(F32)                 # [S, hd] codes
            ik = jnp.trunc(jnp.where(kf == POISON_CODE, 0.0, kf) * step)
            s = jax.lax.dot_general(
                iq_ref[0, n].astype(jnp.bfloat16), ik.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())), preferred_element_type=F32)
            s = jnp.where(live, jnp.abs(s), 0.0)          # [G, S]
            row = theta_ref[0, n]                         # [G, nP_pad]
            for p in range(ppb):
                page = s[:, p * ps:(p + 1) * ps].sum(axis=1, keepdims=True)
                row = jnp.where(lane == blk * ppb + p, page, row)
            theta_ref[0, n] = row

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


def _paged_scout(iq, k_pool, table, counts, kv_len, layer, *, ppb, int_bits,
                 interpret):
    """The kernel call for a given block size ``ppb`` (stacked pool;
    ``hdp_paged_scout`` derives ``ppb`` and documents the rest)."""
    B, N, G, hd = iq.shape
    ps = k_pool.shape[3]
    n_cols = table.shape[1]
    n_pad = -(-n_cols // 128) * 128
    kernel = functools.partial(_kernel, ppb=ppb, n_cols=n_cols,
                               step=pool_scale(int_bits))
    # nxt[j]: the first slot at or after j with a live column (B if
    # none), so a slot's last block can start the next live slot's first
    live = jnp.where(counts > 0, jnp.arange(B, dtype=jnp.int32), B)
    nxt = jnp.append(jax.lax.cummin(live, reverse=True), B).astype(jnp.int32)
    # scalar prefetch (SMEM): the page table, flat — a 2-D SMEM array pads
    # its last dim to 128 words — the counts, extents and the layer index
    pref = (table.reshape(-1).astype(jnp.int32), counts, kv_len, nxt,
            jnp.asarray(layer, jnp.int32).reshape(1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(pref),
        grid=(B,),
        in_specs=[pl.BlockSpec((1, N, G, hd), lambda b, *_: (b, 0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, N, G, n_pad), lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, N, ppb * ps, hd), k_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    theta = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N, G, n_pad), F32),
        # one slot's last block prefetches the next slot's first: the
        # slots run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hdp_paged_scout",
    )(*pref, iq.astype(F32), k_pool)
    return theta[..., :n_cols]


@functools.partial(jax.jit, static_argnames=("int_bits", "interpret"))
def hdp_paged_scout(iq, k_pool, table, kv_len, *, int_bits: int = 4,
                    layer=None, interpret: bool = False):
    """iq [B,N,G,hd] integer parts of one decode query per slot (the
    ``I`` of ``_fixed_split``); k_pool [P,N,ps,hd] the int8 pool of codes
    on the static grid, or the layer-stacked [L,P,N,ps,hd] one with
    ``layer`` the int32 layer to read; table [B,nP] int32 page table;
    kv_len [B] int32 valid positions of each slot (positions
    ``0 .. kv_len - 1``).

    Returns (theta [B,N,G,nP] float32, counts [B] int32): per kv head and
    query head, the abs-sum of the integer scores over each page's valid
    positions (0 past ``counts``), and the table columns each slot walked
    (``ceil(kv_len / ps)``, the pages DMA'd). Columns past ``counts`` are
    never read. The block size comes from the shapes
    (``scout_pages_per_block``).
    """
    if layer is None:
        k_pool, layer = k_pool[None], 0
    ps = k_pool.shape[-2]
    n_cols = table.shape[1]
    kv_len = jnp.clip(kv_len, 0, n_cols * ps).astype(jnp.int32)
    counts = (kv_len + ps - 1) // ps
    ppb = scout_pages_per_block(n_cols, k_pool)
    theta = _paged_scout(iq, k_pool, table, counts, kv_len, layer, ppb=ppb,
                         int_bits=int_bits, interpret=interpret)
    return theta, counts
