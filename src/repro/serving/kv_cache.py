"""KV-cache management for batched serving: dense slots and block pages.

Two layouts, selected by the engine's ``cache_backend``:

* `SlotCache` — the dense per-slot contiguous layout. The decode cache
  for every family is a pytree whose leaves carry a ``batch`` axis (its
  index per leaf comes from ``registry.cache_specs``); ``insert`` copies
  one row of a freshly-prefilled request cache (possibly shorter
  ``max_len`` — bucketed/batched prefill) into a slot, ``clear`` zeroes a
  slot on completion. Works for every family, including recurrent state.
* `PagedKVCache` — the block-paged transformer layout: one shared page
  pool + per-slot page tables, page size = HDP's ``block_k`` so cache
  pages coincide with the integer scout's pruning blocks. The decode
  path gathers only scout-surviving pages (`hdp_paged_decode_attention`)
  — pruned pages are never read, which is the FUM memory-traffic win —
  and pages are allocated per request, which is the resident-bytes win.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import (POISON_CODE, absmax_page_scale, encode_pool,
                              encode_pool_scaled, pool_int_bits, pool_scale)
from repro.models import registry
from repro.serving.allocator import PageAllocator

#: storage formats of the paged pool: int8 codes + per-page scale (the
#: production default), int8 K + fp8 V, or the fp32 A/B oracle.
KV_DTYPES = ("fp32", "int8", "fp8_v")

#: scale calibration of a quantized pool: the static power-of-two grid
#: (bit-parity guarantees) or opt-in per-page calibrated absmax scales.
KV_SCALES = ("grid", "absmax")


class DonatedCacheError(RuntimeError):
    """The live cache handle was donated to a jitted step and not replaced.

    Raised instead of letting XLA hit a deleted buffer: with
    ``donate_argnums`` the decode step aliases the page pool in place, so
    the pre-call handle is dead the moment the call is dispatched.
    Callers must bracket donating calls with ``take()`` / ``put()``.
    """


class _DonatableCache:
    """Mixin guarding the ``cache`` attribute across buffer donation."""

    _cache: Any = None

    @property
    def cache(self):
        if self._cache is None:
            raise DonatedCacheError(
                "KV cache handle was donated to a jitted decode step and "
                "not yet replaced; bracket donating calls with take()/put()")
        return self._cache

    @cache.setter
    def cache(self, value) -> None:
        self._cache = value

    @property
    def donated(self) -> bool:
        """True while the handle is checked out (``take()`` without a
        matching ``put()``/``restore_if_undonated``) — fault-path tests
        assert this is False after an exception unwinds a decode step."""
        return self._cache is None

    def take(self):
        """Hand the live cache out for a donating call; the stored handle
        becomes invalid until ``put`` installs the aliased output."""
        c = self.cache
        self._cache = None
        return c

    def put(self, new_cache) -> None:
        if self._cache is not None:
            raise DonatedCacheError("put() without a prior take()")
        self._cache = new_cache

    def restore_if_undonated(self, cache) -> None:
        """After a failed donating call: re-install the handle unless XLA
        actually consumed (deleted) the donated buffers — the one place
        the donation-detection heuristic lives."""
        if not any(getattr(x, "is_deleted", lambda: False)()
                   for x in jax.tree.leaves(cache)):
            self.put(cache)

    def _donating(self, fn, *args):
        """Run a cache-donating jit with take()/put() bracketing; on a
        trace/compile failure the untouched handle is restored so the
        real error surfaces instead of a later DonatedCacheError."""
        c = self.take()
        try:
            new = fn(c, *args)
        except BaseException:
            self.restore_if_undonated(c)
            raise
        self.put(new)


def _batch_axes(cfg) -> Any:
    """Cache-structured tree of the batch-axis index per leaf."""
    specs = registry.cache_specs(cfg)

    def one(ax):
        ax = tuple(ax)
        return ax.index("batch") if "batch" in ax else None

    return jax.tree.map(one, specs,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            a is None or isinstance(a, str) for a in x))


class SlotCache(_DonatableCache):
    """Slot arithmetic over a family-agnostic cache pytree.

    ``insert``/``clear`` run through donated jits: the serving cache is
    aliased in place instead of re-materialized per call (the same
    zero-copy contract the decode loop has; stale handles raise
    ``DonatedCacheError`` through ``take()``/``put()``).
    """

    def __init__(self, cfg, batch: int, max_len: int, **cache_kw):
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        self.cache = registry.init_cache(cfg, batch, max_len=max_len,
                                         **cache_kw)
        self.axes = _batch_axes(cfg)
        self._ins_jit = jax.jit(
            lambda c, one, slot, row: insert_slot(c, one, slot, self.axes,
                                                  row=row),
            donate_argnums=(0,))
        self._clr_jit = jax.jit(
            lambda c, slot: clear_slot(c, slot, self.axes),
            donate_argnums=(0,))

    # ------------------------------------------------------------- insert
    def insert(self, one_cache, slot, row: int = 0) -> None:
        """Copy row `row` of a request cache into `slot` (donated, in place)."""
        self._donating(self._ins_jit, one_cache,
                       jnp.asarray(slot, jnp.int32), jnp.asarray(row, jnp.int32))

    def clear(self, slot) -> None:
        self._donating(self._clr_jit, jnp.asarray(slot, jnp.int32))


def _dus_axis(big, small, slot, axis: int, row: int = 0):
    """dynamic_update_slice of row `row` of `small` into `big` at index
    `slot` of `axis`, zero-padding the sequence dims when the prefill cache
    is shorter (bucketed/batched prefill)."""
    if small.shape[axis] != 1:
        small = jax.lax.dynamic_slice_in_dim(small, row, 1, axis)
    # pad every non-batch dim that is shorter (bucketed prefill caches)
    pads = []
    for d, (bs, ss) in enumerate(zip(big.shape, small.shape)):
        if d == axis:
            pads.append((0, 0))
        else:
            if ss > bs:
                raise ValueError(
                    f"request cache dim {d} ({ss}) exceeds serving cache "
                    f"({bs})")
            pads.append((0, bs - ss))
    small = jnp.pad(small, pads)
    start = [jnp.asarray(0, jnp.int32)] * big.ndim
    start[axis] = jnp.asarray(slot, jnp.int32)
    return jax.lax.dynamic_update_slice(big, small.astype(big.dtype), start)


def insert_slot(batch_cache, one_cache, slot, axes, row: int = 0) -> Any:
    def one(big, small, ax):
        if ax is None:  # no batch axis (shared leaf) — keep serving copy
            return big
        return _dus_axis(big, small, slot, ax, row=row)

    return jax.tree.map(one, batch_cache, one_cache, axes)


def clear_slot(batch_cache, slot, axes) -> Any:
    def one(big, ax):
        if ax is None:
            return big
        shape = list(big.shape)
        shape[ax] = 1
        return _dus_axis(big, jnp.zeros(shape, big.dtype), slot, ax)

    return jax.tree.map(one, batch_cache, axes)


def cache_bytes(cache) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


# --------------------------------------------------------------------------
# Block-paged KV cache (transformer families)
# --------------------------------------------------------------------------
def default_page_size(cfg) -> int:
    """Tokens per KV page: the HDP key block when HDP is on (pages are the
    scout's pruning blocks), else 16."""
    hdp = cfg.hdp
    return hdp.block_k if hdp is not None and hdp.enabled else 16


def _head_major(pages):
    """[L, n, ps, N, hd] token-major pages <-> [L, n, N, ps, hd] pool
    layout (the swap is its own inverse)."""
    return jnp.swapaxes(pages, 2, 3)


class PagedKVCache(_DonatableCache):
    """Page pool + per-slot page tables, aligned to HDP's ``block_k``.

    Layout: ``k_pages``/``v_pages`` are head-major [L, P, N, page_size,
    hd] pools shared by every slot — one kv head's page is a whole
    [page_size, hd] tile, which the Pallas decode kernel DMAs as stored —
    and a host-side page table maps slot -> page ids.

    ``kv_dtype`` selects the pool storage format:

    * ``"int8"`` (default) — K and V stored as int8 codes on the static
      power-of-two grid (``core.quant.pool_scale``), with per-page
      (per-kv-head) scale arrays ``k_scale``/``v_scale`` [L, P, N]
      written at insert/COW time. The integer and quantized-fraction
      scout copies the decode scout and the self-speculative draft
      stream are *derived views of the codes* (``pool_view_finite``) —
      no extra pools — so drafts, prefix-cached pages and COW tails all
      share one quantized store, and resident cache bytes drop ~4x.
      Dequant is fused into the consumers (gather-time in the XLA
      page-chunk scan, in-register in the Pallas FUM kernel), so pruned
      pages still never DMA.
    * ``"fp8_v"`` — int8 K as above, V stored as float8_e4m3fn (scale
      1.0: the fp8 exponent replaces the per-page scale's job).
    * ``"fp32"`` — the full-precision pool, demoted to an opt-in A/B
      oracle. With HDP enabled an int8 ``k_scout`` pool rides along —
      the write-time-quantized integer copy of K that the decode scout
      always streams, so the full-precision K/V of pruned pages is never
      gathered (the Fetch-Upon-Mask contract; see
      ``attention.hdp_paged_decode_attention``).

    Page 0 is a reserved *scratch* page: pruned pages' gather indices and
    inactive slots' decode writes are redirected there, so its contents
    are arbitrary-but-FINITE and, by construction, always masked. The
    finiteness is load-bearing for K as well as V: an early-head-gated
    head never fetches its pages (gathers read scratch in their place)
    but still runs its softmax before the gate zeroes the output, so
    NaN in scratch K would become NaN * 0 = NaN through the gate — which
    is why the speculative rollback poison explicitly skips the scratch
    page while freed-page poison (never a gather target) is safe.

    Page *ownership* lives in ``self.allocator`` (a refcounted
    `allocator.PageAllocator`): one physical page can back several slots
    plus the prefix cache, and returns to the free list only on its last
    ``unref``. ``assign`` installs an externally-built page list (shared
    prefix pages + owned pages) into a slot's table row; ``alloc`` is the
    allocate-fresh-and-assign convenience the non-sharing paths use.

    Pages are allocated per request for ``prompt + max_new`` tokens (not
    ``max_len``), which is where the serving-memory win over the dense
    per-slot layout comes from; ``active_bytes`` tracks it. All pool
    mutations (``insert``, ``cow``) run through donated jits — the pool
    is aliased in place, never copied per call.

    ``poison_freed`` (debug): poison a page's full-precision K on *true
    free only* — a stale unmasked read of a freed page then surfaces as
    NaN in the scores, while a page still shared by any owner is never
    poisoned. fp32 pools write NaN into ``k_pages``; quantized pools
    write a NaN *sentinel scale* instead (poison must survive
    quantization — NaN has no int8 code), which poisons every stage-3
    dequant of the page while the static-grid scout views stay finite
    (mirroring fp32, where the scout copies were never poisoned). K-only
    either way: V of positions the mask excludes is multiplied by an
    exact 0 but still *read* by XLA, so V-poison would leak NaN through
    legitimate masked reads of reused pages. Reused pages recover their
    scale on first write (insert scatter or the decode scatter's
    scale refresh).
    """

    def __init__(self, cfg, batch: int, max_len: int,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 poison_freed: bool = False,
                 draft_scout: bool = False,
                 kv_dtype: str = "int8",
                 kv_scale: str = "grid",
                 mesh: Optional[jax.sharding.Mesh] = None):
        hdp = cfg.hdp
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")
        if kv_scale not in KV_SCALES:
            raise ValueError(
                f"kv_scale must be one of {KV_SCALES}, got {kv_scale!r}")
        if kv_scale == "absmax" and kv_dtype == "fp32":
            raise ValueError(
                "kv_scale='absmax' calibrates a quantized pool's scales; "
                "fp32 pools have none (use kv_dtype='int8'/'fp8_v')")
        self.kv_dtype = kv_dtype
        self.kv_scale = kv_scale
        self.quantized = kv_dtype != "fp32"
        self.scout = hdp is not None and hdp.enabled
        #: fp32 pools also store the int8 quantized-fraction copy of K at
        #: write time (``f_scout``) when asked: the self-speculative
        #: draft reconstructs its scores from the two int8 copies alone,
        #: so draft steps never read the full-precision K pool. Only
        #: allocated on request — non-speculating engines pay no extra
        #: pool memory. Quantized pools derive both scout copies from
        #: the codes instead, so the flag allocates nothing there.
        self.draft_scout = draft_scout and self.scout
        ps = page_size or default_page_size(cfg)
        if self.scout and ps != hdp.block_k:
            raise ValueError(
                f"page_size {ps} must equal hdp.block_k {hdp.block_k} so "
                "pages coincide with the scout's pruning blocks")
        if (self.scout or self.quantized) and hdp is not None \
                and hdp.enabled and hdp.int_bits > 6:
            raise ValueError(
                f"int_bits={hdp.int_bits} exceeds the int8 scout copy's "
                "range (integer parts reach +/-2^int_bits; need <= 6)")
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        self.page_size = ps
        self.pages_per_slot = -(-max_len // ps)
        self.num_pages = (1 + batch * self.pages_per_slot
                          if num_pages is None else num_pages)
        self.poison_freed = poison_freed
        self.int_bits = pool_int_bits(hdp)
        L, N, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
        dt = jnp.dtype(cfg.dtype)
        shape = (L, self.num_pages, N, ps, hd)
        # leaf name -> (shape, dtype, fill value)
        if self.quantized:
            v_dt = jnp.dtype(jnp.float8_e4m3fn) if kv_dtype == "fp8_v" \
                else jnp.dtype(jnp.int8)
            s0 = pool_scale(self.int_bits)
            leaves = {
                "k_pages": (shape, jnp.int8, 0),
                "v_pages": (shape, v_dt, 0),
                # per-page per-kv-head scales; the scratch page's stays
                # the static grid scale forever (finite by contract)
                "k_scale": ((L, self.num_pages, N), jnp.float32, s0),
                "v_scale": ((L, self.num_pages, N), jnp.float32,
                            1.0 if kv_dtype == "fp8_v" else s0),
            }
        else:
            leaves = {"k_pages": (shape, dt, 0), "v_pages": (shape, dt, 0)}
            if self.scout:
                leaves["k_scout"] = (shape, jnp.int8, 0)
            if self.draft_scout:
                leaves["f_scout"] = (shape, jnp.int8, 0)
        self.mesh = mesh
        self.tp = 1
        shardings = {}
        if mesh is not None:
            from repro.distribution.tp import pool_shardings
            self.tp = int(dict(mesh.shape).get("model", 1))
            if N % self.tp != 0:
                raise ValueError(
                    f"n_kv_heads={N} not divisible by tp={self.tp}")
            # resident pool lives head-sharded: each model shard holds
            # 1/tp of every page's codes, scales and scout views — created
            # in place, so a pool larger than one device's memory fits
            shardings = pool_shardings(mesh, leaves)
        self.cache: Dict[str, jnp.ndarray] = {
            name: jnp.full(shp, fill, dtype, device=shardings.get(name))
            for name, (shp, dtype, fill) in leaves.items()}
        self.allocator = PageAllocator(self.num_pages, reserved=1,
                                       on_free=self._on_free)
        self._slot_pages: Dict[int, List[int]] = {}
        self._slot_floor: Dict[int, int] = {}
        self._table = np.zeros((batch, self.pages_per_slot), np.int32)
        self._table_dev: Optional[jnp.ndarray] = None
        self.peak_pages = 0
        self._insert_jit = jax.jit(self._insert_fn, donate_argnums=(0,))
        self._cow_jit = jax.jit(self._cow_fn, donate_argnums=(0,))
        self._gather_jit = jax.jit(self._gather_fn)

    # ---------------------------------------------------------- host state
    @property
    def _free(self) -> List[int]:
        """Free-list view (read-only; kept for tests/introspection)."""
        return self.allocator._free

    @property
    def pages_in_use(self) -> int:
        """Distinct live pages — slot-owned, shared, or prefix-cached."""
        return self.allocator.in_use

    def slot_pages(self, slot: int) -> List[int]:
        return list(self._slot_pages.get(slot, []))

    def first_owned(self, slot: int) -> int:
        """Index of the first slot-owned (writable) page in the table row;
        earlier entries are shared read-only prefix pages."""
        return self._slot_floor.get(slot, 0)

    def table(self) -> jnp.ndarray:
        """Device copy of the page table, re-uploaded only after
        alloc/free mutate it (steady-state decode uploads nothing)."""
        if self._table_dev is None:
            self._table_dev = jnp.asarray(self._table)
        return self._table_dev

    def assign(self, slot: int, pages: List[int], first_owned: int = 0) -> None:
        """Install `pages` (each already holding one ref owned by this
        slot) as the slot's table row; entries before `first_owned` are
        shared read-only prefix pages the decode write path must never
        touch (enforced by the write floor threaded into the decode jit).
        """
        if slot in self._slot_pages:
            self.free(slot)
        if len(pages) > self.pages_per_slot:
            raise ValueError(
                f"slot {slot}: {len(pages)} pages exceed table width "
                f"{self.pages_per_slot}")
        self._slot_pages[slot] = list(pages)
        self._slot_floor[slot] = first_owned
        self._table[slot, :] = 0
        self._table[slot, :len(pages)] = pages
        self._table_dev = None
        self.peak_pages = max(self.peak_pages, self.pages_in_use)
        if self.poison_freed and self.quantized and pages:
            # a quantized pool's freed-page poison is the NaN sentinel
            # scale; revive it the moment the page re-enters a table row
            # (insert/COW also rewrite it, but decode-growth pages are
            # first touched by the scatter, which writes codes only)
            idx = jnp.asarray(pages, jnp.int32)
            s0 = pool_scale(self.int_bits)
            self.cache = {**self.cache,
                          "k_scale": self.cache["k_scale"].at[:, idx].set(s0)}

    def alloc(self, slot: int, n_tokens: int) -> List[int]:
        """Reserve fresh pages for `n_tokens` cache positions of `slot`."""
        if slot in self._slot_pages:
            self.free(slot)
        need = max(1, -(-n_tokens // self.page_size))
        if need > self.pages_per_slot:
            raise ValueError(
                f"slot {slot}: {n_tokens} tokens exceed max_len {self.max_len}")
        pages = self.allocator.alloc(need)
        self.assign(slot, pages)
        return pages

    def free(self, slot: int) -> None:
        """Release the slot's refs; pages truly free only when unshared."""
        self.allocator.unref(self._slot_pages.pop(slot, []))
        self._slot_floor.pop(slot, None)
        self._table[slot, :] = 0
        self._table_dev = None

    def _on_free(self, pages: List[int]) -> None:
        if self.poison_freed and pages:
            idx = jnp.asarray(pages, jnp.int32)
            if self.quantized:
                # NaN has no int8 code — poison travels through the
                # per-page sentinel scale (every dequant of the page goes
                # NaN; the static-grid scout views stay finite, same as
                # the fp32 pools' unpoisoned scout copies)
                self.cache = {**self.cache,
                              "k_scale": self.cache["k_scale"].at[
                                  :, idx].set(jnp.nan)}
            else:
                self.cache = {**self.cache,
                              "k_pages": self.cache["k_pages"].at[:, idx].set(
                                  jnp.nan)}

    def poison_view(self) -> np.ndarray:
        """Elementwise poison marks of K, shaped like ``k_pages`` — the
        dtype-independent introspection the debug tests assert on (NaN
        under fp32; the -128 sentinel code or a NaN page scale under a
        quantized pool)."""
        kp = np.asarray(self.cache["k_pages"])
        if not self.quantized:
            return np.isnan(kp)
        scl = np.isnan(np.asarray(self.cache["k_scale"]))  # [L, P, N]
        return (kp == POISON_CODE) | scl[:, :, :, None, None]

    # -------------------------------------------------------------- insert
    def _row_to_pages(self, k, row, npg):
        """[L, B, S, N, hd] row -> [L, npg, ps, N, hd] page-shaped."""
        L, _, S, N, hd = k.shape
        ps = self.page_size
        kr = jax.lax.dynamic_index_in_dim(k, row, 1, keepdims=False)
        pad = npg * ps - S
        if pad > 0:
            kr = jnp.pad(kr, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return kr[:, :npg * ps].reshape(L, npg, ps, N, hd)

    def _insert_fn(self, pool, k, v, idx, row):
        """`_insert_pages` under the ``kv.write`` named scope."""
        with jax.named_scope("kv.write"):
            return self._insert_pages(pool, k, v, idx, row)

    def _insert_pages(self, pool, k, v, idx, row):
        """Scatter one request-cache row into the (donated) pool.

        `idx` [pages_per_slot] holds the destination pool page per cache
        page; entries of 0 redirect to the scratch page, which absorbs
        bucket padding and the shared-prefix span without touching any
        real page (scratch content stays arbitrary-but-finite). The
        scatter covers only the pages the request cache can fill
        (ceil(S / ps), a static shape) — a short bucket does not pay a
        pages_per_slot-wide write.
        """
        npg = min(-(-k.shape[2] // self.page_size), self.pages_per_slot)
        kp = self._row_to_pages(k, row, npg)
        vp = self._row_to_pages(v, row, npg)
        flat = idx[:npg].astype(jnp.int32)
        if self.quantized:
            s0 = pool_scale(self.int_bits)
            if self.kv_scale == "absmax":
                # per-page calibrated scales: s = max|x|/127 over the
                # page's positions and head dim, per kv head (all-zero
                # pages fall back to the static step — finite, nonzero)
                ks = absmax_page_scale(kp, self.int_bits)    # [L, npg, N]
                kq = encode_pool_scaled(kp, ks[:, :, None, :, None])
            else:
                ks = jnp.full(kp.shape[:2] + kp.shape[3:4], s0, jnp.float32)
                kq = encode_pool(kp, self.int_bits)
            if self.kv_dtype == "fp8_v":
                vq = vp.astype(pool["v_pages"].dtype)
                vs = jnp.ones_like(ks)
            elif self.kv_scale == "absmax":
                vs = absmax_page_scale(vp, self.int_bits)
                vq = encode_pool_scaled(vp, vs[:, :, None, :, None])
            else:
                vs = jnp.full_like(ks, s0)
                vq = encode_pool(vp, self.int_bits)
            # scales are (re)written with the codes, so a reused page
            # sheds any freed-poison sentinel the moment it holds data
            return {
                "k_pages": pool["k_pages"].at[:, flat].set(_head_major(kq)),
                "v_pages": pool["v_pages"].at[:, flat].set(_head_major(vq)),
                "k_scale": pool["k_scale"].at[:, flat].set(ks),
                "v_scale": pool["v_scale"].at[:, flat].set(vs),
            }
        new = {
            "k_pages": pool["k_pages"].at[:, flat].set(
                _head_major(kp.astype(pool["k_pages"].dtype))),
            "v_pages": pool["v_pages"].at[:, flat].set(
                _head_major(vp.astype(pool["v_pages"].dtype))),
        }
        if self.scout:
            from repro.models.attention import scout_int8
            new["k_scout"] = pool["k_scout"].at[:, flat].set(
                _head_major(scout_int8(kp, self.cfg.hdp)))
        if self.draft_scout:
            from repro.models.attention import scout_frac_int8
            new["f_scout"] = pool["f_scout"].at[:, flat].set(
                _head_major(scout_frac_int8(kp, self.cfg.hdp)))
        return new

    def insert(self, one_cache, slot: int, row: int = 0,
               first_page: int = 0) -> None:
        """Scatter row `row` of a prefill cache into `slot`'s pages.

        Prefill positions past the slot's allocation are bucket padding —
        causally dead and overwritten by decode before they are ever
        visible — and cache pages before `first_page` (a shared prefix
        gathered from the pool, already resident) must not be rewritten:
        both are redirected to the scratch page."""
        pages = self._slot_pages[slot]
        idx = np.zeros(self.pages_per_slot, np.int32)
        idx[first_page:len(pages)] = pages[first_page:]
        self._donating(self._insert_jit, one_cache["k"], one_cache["v"],
                       jnp.asarray(idx), jnp.asarray(row, jnp.int32))

    # ----------------------------------------------------- prefix sharing
    def _cow_fn(self, pool, src, dst):
        return {name: leaf.at[:, dst].set(leaf[:, src])
                for name, leaf in pool.items()}

    def cow(self, src: int, dst: int) -> None:
        """Copy-on-write: duplicate page `src` into owned page `dst`
        (all pools, scout copy included) through the donated pool."""
        self._donating(self._cow_jit, jnp.asarray(src, jnp.int32),
                       jnp.asarray(dst, jnp.int32))

    def _gather_fn(self, pool, idx):
        """Pool pages -> contiguous [L, 1, max_len, N, hd] request cache.

        Positions past the real prefix read the scratch page: arbitrary
        but finite, and masked to an exact-zero contribution by every
        attention path (same contract as bucket padding). Quantized
        pools dequantize here — the request cache a prefix hit seeds
        holds exactly the round-tripped values a cold prefill writes, so
        hot and cold runs stay token-identical."""
        kp = pool["k_pages"]
        L, _, N, ps, hd = kp.shape

        def to_cache(codes, scale):
            g = codes[:, idx]                       # [L, nP, N, ps, hd]
            if scale is not None:
                g = g.astype(jnp.float32) * scale[:, idx][..., None, None]
            g = _head_major(g)                      # [L, nP, ps, N, hd]
            g = g.reshape(L, self.pages_per_slot * ps, N, hd)
            return g[:, None, :self.max_len]

        if self.quantized:
            # prefix pages are live (never freed-poisoned) and hold no
            # rejected-write sentinels (verify rewrites staged positions
            # before a page can enter the prefix cache), so the plain
            # codes * scale dequant is exact here
            return {"k": to_cache(kp, pool["k_scale"]),
                    "v": to_cache(pool["v_pages"], pool["v_scale"])}
        return {"k": to_cache(kp, None), "v": to_cache(pool["v_pages"], None)}

    def gather_prefix(self, pages: List[int]) -> Dict[str, jnp.ndarray]:
        """Build a request cache seeded with the shared prefix pages —
        the cache the suffix-only chunked prefill then appends to."""
        idx = np.zeros(self.pages_per_slot, np.int32)
        idx[:len(pages)] = pages
        return self._gather_jit(self.cache, jnp.asarray(idx))

    # ------------------------------------------------------------ metrics
    def page_bytes(self) -> int:
        """Bytes one page holds over every layer and pool leaf (codes,
        per-page scales, any scout copies) — what sizing a pool by bytes
        divides by."""
        return sum(v.dtype.itemsize * int(np.prod(v.shape[2:]))
                   for v in self.cache.values()) * self.cfg.n_layers

    def active_bytes(self, pages: Optional[int] = None) -> int:
        """Bytes resident for `pages` allocated pages (default: current)."""
        n = self.pages_in_use if pages is None else pages
        return n * self.page_bytes()

    def bytes_per_token(self) -> float:
        """Resident pool bytes per cached token, over every pool leaf
        (codes + per-page scales + any scout copies) — the
        dtype-sensitive footprint the serving summary reports."""
        return self.page_bytes() / self.page_size

    def pool_bytes(self) -> int:
        return cache_bytes(self.cache)

    def pool_bytes_per_shard(self) -> int:
        """Resident pool bytes held by ONE model shard: every pool leaf
        (codes, scales, scout views) is head-sharded, so each of the tp
        shards holds exactly 1/tp of the pool."""
        return self.pool_bytes() // self.tp


def kv_read_bytes_per_step(cfg, seq_len: int, batch: int,
                           hdp_block_sparsity: float = 0.0) -> Tuple[int, int]:
    """(dense, hdp) HBM bytes read from the KV cache per decode step.

    The FUM accounting: pruned KV blocks are never fetched, so HDP decode
    reads ``(1 - sparsity)`` of K/V (the int8 scout copy of K always
    streams). Used by the roofline benchmarks.
    """
    if not hasattr(cfg, "n_kv_heads") or cfg.n_kv_heads == 0:
        return 0, 0
    itemsize = jnp.dtype(cfg.dtype).itemsize
    layers = cfg.n_layers
    kv = 2 * layers * batch * seq_len * cfg.n_kv_heads * cfg.hd * itemsize
    scout = layers * batch * seq_len * cfg.n_kv_heads * cfg.hd  # int8 K
    hdp = int(scout + (1.0 - hdp_block_sparsity) * kv)
    return int(kv), hdp
