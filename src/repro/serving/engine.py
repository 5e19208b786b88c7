"""Batched serving engine with HDP: paged KV cache, batched prefill,
continuous batching.

The engine keeps a fixed pool of ``max_batch`` decode slots over one of
two cache backends:

* ``paged`` (default for transformer families) — a block-paged KV cache
  (`kv_cache.PagedKVCache`): one shared page pool + per-slot page tables,
  page size aligned to HDP's ``block_k`` so cache pages coincide with the
  scout's pruning blocks. Decode reuses the integer scout's per-row keep
  mask to gather only surviving pages — pruned pages are never touched,
  mirroring the FUM kernel's never-DMA'd dataflow — and pages are
  allocated per request (prompt + budget), not per ``max_len`` slot.
* ``dense`` (recurrent families, and the reference A/B) — the seed
  per-slot contiguous `SlotCache`.

Admission is **batched bucketed prefill**: queued requests are grouped by
pad-bucket and stacked at exact batch size into one jitted prefill call
per group (the jit cache stays bounded by max_batch entries per bucket).
Prompts longer than the largest bucket run **chunked prefill**:
bucket-sized chunks appended at a position offset, so arbitrarily long
prompts (up to ``max_len``) prefill through the same jit entries.
Finished slots free their pages and are immediately refillable —
continuous batching.

With the **prefix cache** enabled (paged layout; ``prefix_cache=`` /
``REPRO_PREFIX_CACHE``), admission first walks a token-chunk radix tree
(`allocator.RadixPrefixCache`) for the longest cached prompt prefix:
matched full pages are *shared* into the slot's page table (refcounted by
`allocator.PageAllocator` — no copy, no recompute) and only the prompt
suffix is prefilled, through the same chunked-prefill jit at a position
offset. A full-prompt hit skips prefill entirely after copy-on-write
duplicating the one shared page the decode resume will rewrite. Finished
prompts register their full pages (strictly before the decode write
frontier) back into the tree; under pool pressure, least-recently-used
unreferenced cached pages are evicted. Shared pages are read-only by
construction *and* by enforcement: each slot's first-owned-page offset is
threaded into the decode jit as a write floor — writes below it land in
the scratch page.

The paged backend pins ``hdp.calib = "none"``: its scout copy of K is
quantized at cache-write time, so a data-dependent calibration scale
cannot be honored — the static fixed-point grid applies to prefill and
decode alike (the paper's co-processor model). Under that grid, paged
decode is token-for-token identical to the dense backend.

The decode hot path is **zero-copy and fused**: the serving cache (page
pool or slot cache) is *donated* to the decode and chunked-prefill jits
(``jax.jit(..., donate_argnums=...)``), so per-token cache updates alias
the same buffers instead of allocating a second copy of the pool every
step, and decode runs a jitted ``lax.scan`` over a configurable horizon
(``decode_horizon`` / ``REPRO_DECODE_HORIZON``) — one Python dispatch and
one host sync per H tokens with on-device EOS/budget masking, token-
identical to per-token stepping.

With **self-speculative decode** (``spec_decode`` /
``REPRO_SPEC_DECODE``; supersedes the horizon loop) each step is one
fused draft/verify round instead: ``draft_len - 1`` approximate draft
steps propose tokens by scoring attention from the int8 scout copies
alone (the always-streamed integer copy plus a write-time
quantized-fraction copy — the full-precision K pool is neither read nor
written by a draft step), then ONE ``draft_len``-wide multi-query verify
re-scores every position with full fidelity and per-query-row scout
semantics, reading the page pool once per round instead of once per
token. On-device longest-prefix acceptance commits only exact greedy
tokens (byte-identical to horizon-1 at any acceptance rate), EOS/budget
cuts mirror the horizon loop, and rejected staged writes past the new
frontier are rolled back by NaN-poisoning their K — the write floor
keeps shared prefix pages outside both staging and rollback, so the
allocator/prefix-cache invariants are untouched.

With the **stream scheduler** (``stream_sched`` / ``REPRO_STREAM_SCHED``)
the engine serves a continuous request stream instead of fixed waves:
``submit()`` enqueues into a `scheduler.StreamScheduler` waiting queue,
and every ``step()`` runs one scheduling tick before its decode —
token-budget admission against free slots *and* free-or-evictable pages,
biggest-prefix-cache-hit-first ordering, in-flight recycling of slots
vacated mid-run, and long cold prompts chunk-prefilled a slice per step
so the running batch keeps decoding underneath them. A watchdog raises
instead of spinning when nothing can ever be admitted. The streaming
``serve()`` generator yields Results in completion order, and
per-request TTFT / TPOT / queue-wait plus queue-depth aggregates land in
``summary()``. Scheduling only reorders *admission*; per-slot compute is
untouched, so outputs stay byte-identical to static-wave serving (and to
solo runs — the equivalence tests/test_serving.py pins).

HDP is active inside both prefill and decode attention when
``cfg.hdp.enabled`` — stats (block/head/page sparsity per layer) are
aggregated into engine metrics so serving examples/benchmarks can report
the achieved sparsity next to throughput. Attention implementation and
cache layout are selected by an ``repro.attention.AttnSpec``
(``attn=AttnSpec(backend="pallas")`` routes the paged HDP decode through
the block-sparse Pallas kernel, interpret mode off-TPU); the resolved
backend per phase is reported by ``summary()``. The old
``cache_backend=``/``attn_backend=`` string kwargs keep working for one
release through a deprecation shim.
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.attention import (AttnSpec, DraftProfile, default_spec,
                             effective_policy, known_backend_names,
                             resolve_backend, spec_from_legacy)
from repro.configs.base import ModelConfig
from repro.models import registry
from repro.models.attention import build_attn_call
from repro.serving import kv_cache
from repro.serving.allocator import PoolExhausted, RadixPrefixCache
from repro.serving.faults import FaultInjector, FaultPlan, coerce_injector
from repro.serving.scheduler import (QueueFull, SchedulerConfig,
                                     StreamScheduler)
from repro.serving.tracing import SpanRecorder

I32 = jnp.int32

#: Families served through the block-paged transformer KV cache.
PAGEABLE_FAMILIES = ("dense", "moe", "vlm")

#: env var giving the default decode horizon (explicit kwargs win).
HORIZON_ENV = "REPRO_DECODE_HORIZON"

#: env var enabling prompt-prefix page sharing when ``prefix_cache=None``
#: is passed (explicit kwargs win; ignored for layouts that cannot share).
PREFIX_ENV = "REPRO_PREFIX_CACHE"

#: env var enabling self-speculative decode when ``spec_decode=None`` is
#: passed (explicit kwargs win; degrades silently for families that
#: cannot speculate — recurrent state has no multi-query verify).
SPEC_ENV = "REPRO_SPEC_DECODE"

#: env var giving the default draft length (explicit kwargs win).
DRAFT_ENV = "REPRO_DRAFT_LEN"

#: env var enabling the continuous-batching stream scheduler when
#: ``stream_sched=None`` is passed (explicit kwargs win).
STREAM_ENV = "REPRO_STREAM_SCHED"

#: env var enabling acceptance-adaptive speculation when
#: ``adaptive_spec=None`` is passed (explicit kwargs win; the env default
#: degrades silently when speculative decode itself is off).
ADAPTIVE_ENV = "REPRO_ADAPTIVE_SPEC"

#: env var giving the paged pool's KV storage dtype when the AttnSpec
#: leaves ``kv_dtype="auto"`` (explicit specs win; "int8" when unset —
#: the quantized pool is the production default and fp32 the opt-in
#: A/B oracle). Dense layouts always serve fp32.
KV_DTYPE_ENV = "REPRO_KV_DTYPE"

#: env var giving the default tensor-parallel degree when ``tp=None`` and
#: no mesh is passed (explicit kwargs win). A value the engine cannot
#: honour — a dense layout, a head count it does not divide, too few
#: devices — raises, exactly as the kwarg does.
MESH_TP_ENV = "REPRO_MESH_TP"

class TensorParallelUnsupported(ValueError):
    """A tensor-parallel degree the engine cannot serve at: a dense
    layout or a kv-head count it does not divide. (Too few devices is
    the serving mesh's RuntimeError.)"""


def resolve_tp(tp=None, mesh=None, *, cfg=None, layout=None) -> int:
    """The tensor-parallel degree an engine serves at.

    ``mesh``'s "model" axis if a mesh is given (``tp`` must agree), else
    ``tp``, else ``REPRO_MESH_TP``, else 1. A degree above 1 that cannot
    be honoured raises ``TensorParallelUnsupported`` — from the kwarg and
    the env alike, since a silent tp=1 would serve unsharded unnoticed.
    ``cfg``/``layout`` enable the head-count and layout checks.
    """
    if mesh is not None:
        mesh_tp = int(dict(mesh.shape).get("model", 1))
        if tp is not None and int(tp) != mesh_tp:
            raise ValueError(
                f"tp={tp} disagrees with the mesh's model axis ({mesh_tp})")
        tp = mesh_tp
    if tp is None:
        env = os.environ.get(MESH_TP_ENV, "")
        try:
            tp = int(env) if env else 1
        except ValueError:
            raise ValueError(f"{MESH_TP_ENV}={env!r}: not an int")
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp > 1:
        if layout is not None and layout != "paged":
            raise TensorParallelUnsupported(
                "tp > 1 shards the paged page pool along the head axis; "
                "dense-layout families cannot serve sharded")
        if cfg is not None and cfg.n_kv_heads % tp != 0:
            raise TensorParallelUnsupported(
                f"n_kv_heads={cfg.n_kv_heads} not divisible by tp={tp}")
    return tp


#: env var giving the default engine-replica count for launch/serve.py's
#: ``--dp`` flag (the Engine itself is one replica; see serving/replica.py).
MESH_DP_ENV = "REPRO_MESH_DP"


@dataclasses.dataclass
class Request:
    uid: int
    prompt: Sequence[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    #: admission priority ("prefix" order mode): higher admits first, and
    #: only a strictly-lower-priority running request may be preempted to
    #: unblock a starved queue head (equal priorities never preempt).
    priority: int = 0
    #: wall-clock budget from submit() to completion; on expiry the
    #: request is cancelled with ``Result(status="deadline")`` wherever
    #: it is (queued, mid-prefill, or decoding).
    deadline_s: Optional[float] = None
    #: wall-clock budget from submit() to slot activation; expires only
    #: while still waiting (an admitted request is allowed to finish).
    max_queue_wait_s: Optional[float] = None
    # --- preempt/failover restore bookkeeping (engine-managed) ---
    #: tokens already generated before the last preempt/failover; they are
    #: folded into ``prompt`` for the recompute resume and re-emitted at
    #: the head of the final ``Result.tokens``.
    prior_tokens: Tuple[int, ...] = ()
    #: prompt length of the ORIGINAL submission (``prompt`` grows with
    #: each restore); None until the first preemption.
    orig_prompt_len: Optional[int] = None
    #: times this request was preempted or failed over so far.
    preemptions: int = 0


@dataclasses.dataclass
class Result:
    uid: int
    prompt_len: int
    tokens: List[int]
    #: host seconds of the request's ``engine.prefill`` span (a batched
    #: group's span shared equally): the time to dispatch its prefill
    #: programs, which run on the device asynchronously
    prefill_s: float = 0.0
    decode_steps: int = 0
    #: False when Engine.run exhausted its step budget before this request
    #: finished (tokens then hold the partial generation so far), and for
    #: every non-"ok" status.
    complete: bool = True
    #: "ok" | "cancelled" | "deadline" | "error" — the typed request
    #: outcome; non-"ok" Results carry whatever tokens were generated
    #: before the request was unwound.
    status: str = "ok"
    #: human-readable failure detail for non-"ok" statuses.
    error: Optional[str] = None
    #: times the request was preempted/failed over before finishing
    #: (its tokens are byte-identical to an uninterrupted run regardless).
    preemptions: int = 0
    #: seconds from submit() to slot activation (queue + prefill wait);
    #: None for requests served without a submit timestamp.
    queue_wait_s: Optional[float] = None
    #: seconds from submit() to the first generated token, at host-sync
    #: granularity: every token of one fused horizon/spec round shares
    #: that round's single sync timestamp.
    ttft_s: Optional[float] = None
    #: mean seconds per token after the first (same sync granularity;
    #: None when fewer than two tokens were generated).
    tpot_s: Optional[float] = None


class Engine:
    """Single-host serving engine (mesh-aware variants run via launch/serve).

    Parameters
    ----------
    cfg: ModelConfig (reduced configs run on CPU).
    params: model params; freshly initialized when None.
    max_batch: decode slot count.
    max_len: serving cache length (prompt + generation must fit).
    prefill_buckets: pad-to lengths for the prefill jit cache.
    collect_stats: aggregate HDP sparsity stats (small overhead).
    attn: AttnSpec (or a backend name/tag string) selecting both the
        attention backend (auto | reference | xla | pallas | an exact
        registry name) and the serving cache layout
        (``AttnSpec(layout=...)``: auto = paged for transformer families,
        dense otherwise). None uses the default spec (honors the
        REPRO_ATTN_BACKEND env var).
    cache_backend / attn_backend: DEPRECATED string kwargs, mapped onto
        ``attn`` via a shim for one release (emits a DeprecationWarning).
    page_size: paged-layout page length; defaults to ``hdp.block_k``
        (must match it while HDP is enabled).
    num_pages: page-pool size override (default: one full table per slot
        plus the scratch page). A larger pool gives evicted-under-
        pressure prefix pages more room to stay resident.
    prefix_cache: share prompt-prefix pages across requests through the
        refcounted radix tree (paged layout only). None reads the
        ``REPRO_PREFIX_CACHE`` env var and degrades silently when the
        layout cannot share (dense, non-rope positions, HDP chunk
        misalignment); passing True explicitly raises instead.
    decode_horizon: tokens generated per jitted decode call (the fused
        ``lax.scan`` loop) — one Python dispatch + one host sync per
        horizon instead of per token. Token-identical to horizon=1:
        EOS/budget masking runs on device, and the scan length is
        clamped per call to the longest remaining budget so the loop
        never runs steps that provably have no active slot. None reads
        ``REPRO_DECODE_HORIZON`` (default 1). Admission (slot refill)
        happens at horizon boundaries.
    spec_decode: self-speculative decode — each engine step runs ONE
        fused round of ``draft_len - 1`` approximate draft steps (the
        draft profile's cheap attention proposes tokens) plus one
        ``draft_len``-wide multi-query verify over the serving cache
        (the page pool is read once per round instead of once per
        token), with on-device longest-prefix accept, EOS/budget cuts
        and NaN-poison rollback of rejected speculative K writes.
        Exact-match acceptance makes the output token-identical to
        horizon-1 greedy decode, at any acceptance rate. Supersedes the
        ``decode_horizon`` loop when enabled. None reads
        ``REPRO_SPEC_DECODE`` and degrades silently for families whose
        cache cannot verify (recurrent state); passing True explicitly
        raises instead. Pins ``hdp.calib = "none"`` like the paged
        layout does: speculative staging leaves garbage past the commit
        frontier, which a data-dependent calibration scale would see.
    draft_len: tokens proposed+verified per speculative round (the
        verify width; committed tokens per round are 1..draft_len).
        None reads ``REPRO_DRAFT_LEN`` (default 4).
    draft_profile: DraftProfile selecting the draft pass's approximate
        attention (score source + survival-threshold overrides); None
        uses the default profile (scout-copy scores, exact-pass
        thresholds).
    adaptive_spec: acceptance-adaptive speculation — a
        `repro.autotune.SpecController` keeps a running acceptance-rate
        EMA and re-plans the draft length (1..draft_len) and the draft
        profile's prune aggressiveness before every round. Committed
        tokens stay byte-identical at any plan (exact-match acceptance
        — the knobs only move the work/acceptance tradeoff). None reads
        ``REPRO_ADAPTIVE_SPEC`` and degrades silently when spec decode
        is off; passing True explicitly without spec_decode raises.
    tuner: explicit `repro.autotune.Tuner` to install as the process
        default (shared by cost-policy dispatch everywhere; engines are
        traced against the process tuner because backend selection
        happens inside jit traces). None keeps the current default —
        created lazily, warm-started from ``REPRO_TUNER_CACHE``.
    stream_sched: continuous-batching stream scheduler —
        ``submit()`` enqueues into a waiting queue and every step runs
        one `scheduler.StreamScheduler` tick (token-budget admission,
        prefix-hit-first ordering, mid-run slot recycling, interleaved
        chunked prefill, watchdog) before decoding. Composes with every
        decode mode (horizon, prefix cache, spec decode) and never
        changes per-request tokens — only admission timing/order. None
        reads ``REPRO_STREAM_SCHED`` (default off); passing a ``sched``
        config implies True.
    sched: SchedulerConfig tuning the scheduler (chunk token budget per
        step, admission order, watchdog limits); None uses defaults.
    faults: deterministic fault injection — a `serving.faults`
        FaultInjector (share one across a ReplicaSet for fleet-wide
        once-only events), FaultPlan, or plan spec string. None reads
        ``REPRO_FAULT_PLAN`` (default: no injection). Step numbers in
        the plan count this engine's ``step()`` calls from construction.
    """

    def __init__(self, cfg: ModelConfig, params=None, *, rng=None,
                 max_batch: int = 4, max_len: int = 128,
                 prefill_buckets: Sequence[int] = (32, 64, 128),
                 collect_stats: bool = False,
                 attn: Optional[AttnSpec] = None,
                 cache_backend: Optional[str] = None,
                 attn_backend: Optional[str] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 decode_horizon: Optional[int] = None,
                 spec_decode: Optional[bool] = None,
                 draft_len: Optional[int] = None,
                 draft_profile: Optional[DraftProfile] = None,
                 adaptive_spec: Optional[bool] = None,
                 tuner=None,
                 stream_sched: Optional[bool] = None,
                 sched: Optional[SchedulerConfig] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 tp: Optional[int] = None,
                 faults: Union[FaultInjector, FaultPlan, str, None] = None):
        if cfg.is_encoder_decoder:
            raise NotImplementedError(
                "enc-dec serving uses launch/serve.py --arch whisper path")
        if isinstance(attn, str):
            attn = AttnSpec(backend=attn)
        spec = attn if attn is not None else default_spec()
        if attn_backend is not None or cache_backend is not None:
            spec = spec_from_legacy(attn_backend, cache_backend, base=spec)
        for phase in ("prefill", "decode"):
            req = spec.requested_for(phase)
            if req != "auto" and req not in known_backend_names():
                raise ValueError(
                    f"unknown attention backend {req!r} ({phase}); "
                    f"known: {known_backend_names()}")
        layout = spec.layout
        if layout == "auto":
            layout = ("paged" if cfg.family in PAGEABLE_FAMILIES else "dense")
        if layout == "paged" and cfg.family not in PAGEABLE_FAMILIES:
            raise ValueError(
                f"family {cfg.family!r} has no KV pages; use dense layout")
        kv_dtype = spec.kv_dtype
        if kv_dtype == "auto":
            kv_dtype = os.environ.get(KV_DTYPE_ENV, "") or "int8"
            if kv_dtype not in kv_cache.KV_DTYPES:
                raise ValueError(
                    f"{KV_DTYPE_ENV}={kv_dtype!r}: must be one of "
                    f"{kv_cache.KV_DTYPES}")
        if layout != "paged":
            kv_dtype = "fp32"     # dense slot caches have no quantized store
        # pin the resolved dtype back into the spec: attn_apply keys its
        # prefill round-trip (and nothing else) off attn.kv_dtype, so the
        # spec the jits close over must carry the concrete value
        spec = spec.replace(kv_dtype=kv_dtype)
        self.kv_dtype = kv_dtype
        if (layout == "paged" and cfg.hdp is not None
                and cfg.hdp.enabled and cfg.hdp.calib != "none"):
            # write-time scout quantization cannot honor a data-dependent
            # calibration scale; pin the static grid for prefill + decode
            # alike so the engine stays self-consistent (and identical to
            # the dense backend under the same effective config)
            cfg = cfg.replace(hdp=cfg.hdp.replace(calib="none"))
        spec_capable = cfg.family in PAGEABLE_FAMILIES
        if spec_decode is None:
            env = os.environ.get(SPEC_ENV, "")
            spec_decode = env.lower() in ("1", "true", "on") if env else False
            spec_decode = spec_decode and spec_capable   # env default degrades
        elif spec_decode and not spec_capable:
            raise ValueError(
                f"spec_decode=True: family {cfg.family!r} has no multi-query "
                "verify path (recurrent state cannot re-score draft "
                "positions against a cache)")
        self.spec = bool(spec_decode)
        if draft_len is None:
            draft_len = int(os.environ.get(DRAFT_ENV, "4") or 4)
        if draft_len < 1:
            raise ValueError(f"draft_len must be >= 1, got {draft_len}")
        self.draft_len = int(draft_len)
        self.draft_profile = draft_profile if draft_profile is not None \
            else DraftProfile()
        if adaptive_spec is None:
            env = os.environ.get(ADAPTIVE_ENV, "")
            adaptive_spec = env.lower() in ("1", "true", "on") if env else False
            adaptive_spec = adaptive_spec and self.spec   # env default degrades
        elif adaptive_spec and not self.spec:
            raise ValueError(
                "adaptive_spec=True requires spec_decode (there is no "
                "draft length to adapt without speculative rounds)")
        self.spec_ctl = None
        if adaptive_spec:
            from repro.autotune import SpecConfig, SpecController
            self.spec_ctl = SpecController(
                self.draft_profile,
                cfg.hdp if cfg.hdp is not None and cfg.hdp.enabled else None,
                SpecConfig(k_max=self.draft_len))
        if (self.spec and layout != "paged" and cfg.hdp is not None
                and cfg.hdp.enabled and cfg.hdp.calib != "none"):
            # the paged pinning above, for the same reason seen from the
            # other side: rejected speculative writes leave garbage (or
            # rollback poison) past the commit frontier, which a
            # data-dependent calibration scale computed over the cache
            # extent would observe — breaking token identity with the
            # non-speculative baseline
            cfg = cfg.replace(hdp=cfg.hdp.replace(calib="none"))
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.buckets = sorted(b for b in prefill_buckets if b <= max_len) \
            or [max_len]
        self.collect_stats = collect_stats
        self.paged = layout == "paged"
        self.attn_spec = spec
        self.policy = effective_policy(spec)
        self.tuner = None
        if tuner is not None:
            # backend selection happens inside jit traces, which consult
            # the process-default tuner — install the explicit one there
            from repro.autotune import set_default_tuner
            set_default_tuner(tuner)
        if self.policy == "cost":
            from repro.autotune import default_tuner
            self.tuner = default_tuner()
        # static retrace token for the decode/spec AND prefill/chunk jits:
        # bumped when a flushed probe flips a tuner decision, so exactly
        # the affected programs re-trace (and re-consult the tuner).
        self._attn_epoch = 0
        if decode_horizon is None:
            decode_horizon = int(os.environ.get(HORIZON_ENV, "1") or 1)
        if decode_horizon < 1:
            raise ValueError(f"decode_horizon must be >= 1, got {decode_horizon}")
        self.horizon = int(decode_horizon)

        # ---- serving mesh (tensor-parallel paged attention) ----
        if spec.kv_scale == "absmax" and kv_dtype == "fp32":
            raise ValueError(
                "kv_scale='absmax' calibrates a quantized pool's scales; "
                "it needs kv_dtype='int8'/'fp8_v' and the paged layout")
        self.kv_scale = spec.kv_scale if layout == "paged" else "grid"
        tp = resolve_tp(tp, mesh, cfg=cfg, layout=layout)
        if tp > 1:
            if mesh is None:
                from repro.launch.mesh import make_serving_mesh
                mesh = make_serving_mesh(tp=tp)
            self.mesh, self.tp = mesh, tp
        else:
            self.mesh, self.tp = None, 1

        if params is None:
            rng = rng if rng is not None else jax.random.PRNGKey(0)
            params, _ = registry.init_params(cfg, rng)
        if self.mesh is not None:
            # one replicated copy per mesh device, placed once: a tree left
            # on one device would be re-broadcast by every jitted call
            params = jax.device_put(params, jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec()))
        self.params = params

        if self.paged:
            self.pages = kv_cache.PagedKVCache(
                cfg, max_batch, max_len, page_size=page_size,
                num_pages=num_pages,
                # the draft's scores come from the int8 scout copies; the
                # quantized-fraction copy is only worth pool memory when a
                # *fp32* pool speculates with scout-copy scores (quantized
                # pools derive both scout views from the codes for free)
                draft_scout=self.spec and self.draft_profile.scores == "scout",
                kv_dtype=kv_dtype, kv_scale=self.kv_scale, mesh=self.mesh)
        else:
            # speculative rounds stage writes up to draft_len - 1 positions
            # past the commit frontier before rolling back; the dense slot
            # cache carries that margin so staged writes near max_len can
            # never clamp onto (and corrupt) committed positions. The
            # positions are causally invisible until rewritten, exactly
            # like bucket padding. (The paged layout needs no margin: its
            # write path scratch-redirects past-the-table columns.)
            margin = self.draft_len - 1 if self.spec else 0
            self.slots = kv_cache.SlotCache(cfg, max_batch, max_len + margin)
        self.prefix = self._build_prefix_cache(prefix_cache)
        self._free = list(range(max_batch))
        self._active: Dict[int, Dict[str, Any]] = {}  # slot -> request state
        self._results: Dict[int, Result] = {}
        self._queue: List[Request] = []
        self._last_tok = jnp.zeros((max_batch, 1), I32)
        self._pos = jnp.zeros((max_batch,), I32)
        # device-resident per-slot decode state: written at install time,
        # refreshed from the fused loop's own carry after every horizon —
        # the steady-state decode step uploads no host arrays at all
        self._active_dev = jnp.zeros((max_batch,), bool)
        self._remaining_dev = jnp.zeros((max_batch,), I32)
        self._eos_dev = jnp.full((max_batch,), -1, I32)
        # per-slot first-owned-page offset: table entries below it are
        # shared read-only prefix pages; the decode write path redirects
        # anything below the floor to the scratch page
        self._floor_dev = jnp.zeros((max_batch,), I32)
        #: host spans of the engine's phases (``engine.*``): the one
        #: timer behind ``summary()['prefill_s']``/``['decode_s']``
        self.spans = SpanRecorder()
        self.metrics: Dict[str, float] = self._fresh_metrics()
        #: submit() timestamps per uid (popped at finish) and the finish
        #: order log the streaming serve() generator drains
        self._t_submit: Dict[int, float] = {}
        self._finished: List[int] = []
        #: uid -> (absolute deadline, absolute queue-wait deadline),
        #: enforced at the top of every step; popped at finish
        self._deadlines: Dict[int, Tuple[Optional[float],
                                         Optional[float]]] = {}
        #: activation sequence counter — the preemption victim tiebreak
        #: (newest activation preempts first: it has the least sunk work)
        self._act_seq = 0
        #: engine step counter driving the fault plan's step schedule
        self._cur_step = 0
        self.faults = coerce_injector(faults)
        #: cached all-false logit-poison mask for fault-free steps (the
        #: jitted decode/verify always takes the mask so injection never
        #: changes the compiled program)
        self._zero_inject = jnp.zeros((max_batch,), bool)
        if stream_sched is None:
            env = os.environ.get(STREAM_ENV, "")
            stream_sched = env.lower() in ("1", "true", "on") if env \
                else sched is not None
        self.sched = StreamScheduler(self, sched or SchedulerConfig()) \
            if stream_sched else None

        # buffer donation: the serving cache (page pool / slot cache) is
        # aliased in place by the batched-prefill, chunked-prefill and
        # decode jits instead of copied per call; take()/put() on the
        # cache objects keep stale host handles from being reused after a
        # donating call. Batched prefill fuses the prompt forward with the
        # page/slot scatter in one donated jit, so no undonated O(pool)
        # insert copy remains on the admission path.
        self._prefill_jit = jax.jit(
            self._prefill_paged_fn if self.paged else self._prefill_dense_fn,
            static_argnums=(2, 3), donate_argnums=(4,))
        self._chunk_jit = jax.jit(self._prefill_chunk_fn,
                                  static_argnums=(2,), donate_argnums=(3,))
        # static argnums: scan length / draft plan + the attention epoch
        # (cost-policy retrace token); the spec round also threads the
        # round's DraftProfile statically so the adaptive controller can
        # swap profiles at a bounded number of compile entries
        self._decode_jit = jax.jit(
            self._decode_loop_paged_fn if self.paged
            else self._decode_loop_dense_fn,
            static_argnums=(0, 1), donate_argnums=(4,))
        self._spec_jit = jax.jit(
            self._spec_round_paged_fn if self.paged
            else self._spec_round_dense_fn,
            static_argnums=(0, 1, 2), donate_argnums=(5,))

    # ------------------------------------------------------------ serving mesh
    def _mesh_ctx(self):
        """Ambient-mesh context every jitted step runs under: at trace
        time the model layer consults it to route paged-decode attention
        through the head-sharded shard_map wrapper (a no-op context when
        the engine is unsharded)."""
        from repro.distribution.tp import serving_mesh
        return serving_mesh(self.mesh)

    # ------------------------------------------------------------ prefix cache
    def _build_prefix_cache(self, requested) -> Optional[RadixPrefixCache]:
        capable = self.paged and self._can_chunk
        if requested is None:
            env = os.environ.get(PREFIX_ENV, "")
            requested = env.lower() in ("1", "true", "on") if env else False
            requested = requested and capable   # env default degrades
        if not requested:
            return None
        if not self.paged:
            raise ValueError(
                "prefix_cache=True requires the paged cache layout "
                "(AttnSpec(layout='paged'))")
        if not self._can_chunk:
            raise ValueError(
                "prefix_cache=True needs offset-capable prefill (rope "
                "positions, HDP chunk boundaries on block_q) — this config "
                "cannot prefill a prompt suffix in isolation")
        return RadixPrefixCache(self.pages.allocator, self.pages.page_size)

    @property
    def _page_align(self) -> int:
        """Pages per shareable unit: a match boundary must sit on an HDP
        q-block boundary or the suffix scout would pool across it."""
        hdp = self.cfg.hdp
        if hdp is not None and hdp.enabled:
            return math.lcm(self.pages.page_size, hdp.block_q) \
                // self.pages.page_size
        return 1

    # ------------------------------------------------------------ jitted fns
    def _prefill_body(self, params, tokens, bucket_len):
        cache = registry.init_cache(self.cfg, tokens.shape[0],
                                    max_len=bucket_len)
        batch = {"tokens": tokens}
        _, new_cache, stats = registry.apply_prefill(
            self.cfg, params, batch, cache,
            collect_stats=self.collect_stats, attn=self.attn_spec)
        return new_cache, stats

    def _prefill_paged_fn(self, params, tokens, bucket_len, epoch, pool,
                          page_idx):
        """Batched prefill fused with the page scatter, pool donated.

        ``page_idx`` [nb, pages_per_slot]: destination pool page per
        request-cache page (0-padded — the scratch page absorbs bucket
        padding, exactly as in `PagedKVCache.insert`)."""
        del epoch  # static retrace token only — selection reruns per trace
        one_cache, stats = self._prefill_body(params, tokens, bucket_len)
        for r in range(tokens.shape[0]):
            pool = self.pages._insert_fn(pool, one_cache["k"],
                                         one_cache["v"], page_idx[r], r)
        return pool, stats

    def _prefill_dense_fn(self, params, tokens, bucket_len, epoch,
                          slot_cache, slots):
        """Batched prefill fused with the slot insert, slot cache donated."""
        del epoch
        one_cache, stats = self._prefill_body(params, tokens, bucket_len)
        for r in range(tokens.shape[0]):
            slot_cache = kv_cache.insert_slot(slot_cache, one_cache,
                                              slots[r], self.slots.axes,
                                              row=r)
        return slot_cache, stats

    def _prefill_chunk_fn(self, params, tokens, epoch, cache, offset):
        del epoch  # static retrace token only
        _, new_cache, stats = registry.apply_prefill(
            self.cfg, params, {"tokens": tokens}, cache,
            collect_stats=self.collect_stats, pos_offset=offset,
            attn=self.attn_spec)
        return new_cache, stats

    def _decode_step(self, params, token, cache, pos, table, floors=None,
                     inject=None):
        if table is not None:
            logits, new_cache, stats = registry.apply_decode(
                self.cfg, params, token, cache, pos[:, None],
                collect_stats=self.collect_stats, page_table=table,
                write_floor=floors, attn=self.attn_spec)
        else:
            logits, new_cache, stats = registry.apply_decode(
                self.cfg, params, token, cache, pos[:, None],
                collect_stats=self.collect_stats, attn=self.attn_spec)
        with jax.named_scope("lm_head"):
            if inject is not None:
                # fault harness: poison the selected rows' logits so the
                # tripwire below fires exactly as it would for organic NaNs
                logits = jnp.where(inject[:, None, None], jnp.nan, logits)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(I32)[:, None]
            # per-slot tripwire: a non-finite logit row means this request's
            # state is poisoned (overflow, stale staging read, bad page) —
            # flag it so the host can abort ONLY that request while the rest
            # of the batch keeps its token-identical stream
            bad = ~jnp.isfinite(logits[:, -1]).all(axis=-1)
        return nxt, bad, new_cache, stats

    def _decode_loop(self, length, params, tok, cache, table, floors, pos,
                     active, remaining, eos, inject):
        """``length`` fused decode steps as one jitted lax.scan.

        On-device bookkeeping mirrors the host loop exactly: a slot is
        done when its budget runs out (``remaining``) or it emits its
        ``eos`` id (-1 = none); done slots park on token 0 / position 0
        with their page-table row zeroed, so their writes land in the
        scratch page. A slot whose logits go non-finite (the per-slot
        tripwire; ``inject`` forces it for the fault harness) parks the
        same way but is reported faulted instead of emitting its token.
        Emitted per step: (token [B], pre-step active mask [B], fault
        mask [B], stats) — the active mask tells the host which emitted
        tokens are real, keeping horizon-H output token-identical to H=1
        even when EOS fires mid-horizon. ``length`` is static (the host
        clamps it to the longest remaining budget, so the scan never
        runs steps that provably have no active slot; at most
        ``horizon`` distinct compile entries exist per engine).
        """
        def body(carry, _):
            tok, cache, pos, active, remaining = carry
            table_eff = (None if table is None
                         else jnp.where(active[:, None], table, 0))
            nxt, bad, cache2, stats = self._decode_step(
                params, tok, cache, pos, table_eff, floors, inject)
            fault = active & bad
            done = active & ~fault & ((remaining <= 1)
                                      | ((eos >= 0) & (nxt[:, 0] == eos)))
            gone = done | fault
            carry = (jnp.where(gone[:, None], 0, nxt), cache2,
                     jnp.where(gone, 0, pos + 1), active & ~gone,
                     remaining - active.astype(I32))
            return carry, (nxt[:, 0], active, fault, stats)

        carry, ys = jax.lax.scan(body, (tok, cache, pos, active, remaining),
                                 None, length=length)
        tok, cache, pos, active, remaining = carry
        return ys, tok, cache, pos, active, remaining

    def _decode_loop_paged_fn(self, length, epoch, params, tok, cache, table,
                              floors, pos, active, remaining, eos, inject):
        del epoch  # static retrace token only — selection reruns per trace
        return self._decode_loop(length, params, tok, cache, table, floors,
                                 pos, active, remaining, eos, inject)

    def _decode_loop_dense_fn(self, length, epoch, params, tok, cache, pos,
                              active, remaining, eos, inject):
        del epoch
        return self._decode_loop(length, params, tok, cache, None, None,
                                 pos, active, remaining, eos, inject)

    # ------------------------------------------------------ speculative round
    def _draft_step(self, params, token, cache, pos, table, floors,
                    profile):
        """One approximate draft decode step (cheap attention per the
        round's DraftProfile; never collects stats)."""
        kw = {"page_table": table, "write_floor": floors} \
            if table is not None else {}
        logits, new_cache, _ = registry.apply_decode(
            self.cfg, params, token, cache, pos[:, None],
            collect_stats=False, attn=self.attn_spec,
            draft=profile, **kw)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(I32)[:, None]
        return nxt, new_cache

    def _verify_step(self, params, tokens, cache, pos_rows, table, floors,
                     inject=None):
        """One k-wide multi-query verify: all k positions re-scored (and
        their exact K/V re-written, overwriting the draft's staging) in a
        single batched attention call over the serving cache. Rows with
        any non-finite logit (or forced by ``inject``) are reported
        faulted — the round commits nothing for them."""
        kw = {"page_table": table, "write_floor": floors} \
            if table is not None else {}
        logits, new_cache, stats = registry.apply_decode(
            self.cfg, params, tokens, cache, pos_rows,
            collect_stats=self.collect_stats, attn=self.attn_spec, **kw)
        if inject is not None:
            logits = jnp.where(inject[:, None, None], jnp.nan, logits)
        bad = ~jnp.isfinite(logits).all(axis=(1, 2))
        return jnp.argmax(logits, axis=-1).astype(I32), bad, new_cache, stats

    def _poison_rejected(self, cache, table_eff, floors, pos, n_commit,
                         active, k):
        """Rollback fence: NaN-poison the K of rejected speculative writes.

        Positions ``pos + n_commit .. pos + k - 1`` hold K/V of tokens
        the verify refuted; by construction they are rewritten before any
        masked read can see them, and this poison makes that invariant
        self-enforcing — a stale read would surface as NaN in the logits
        instead of a silent wrong token. K-only, like the allocator's
        freed-page poison: masked V reads still multiply by exact zeros.
        Sub-floor (shared, read-only) pages are never poisoned — the
        write fences are the SAME the K/V scatter honors
        (models.attention.resolve_write_pages).

        The gather-then-writeback shape is load-bearing, not a missed
        optimization: non-rejected lanes must NOT be redirected into the
        scratch page, because scratch K is subject to the pool-wide
        arbitrary-but-FINITE contract — an early-head-gated head's pages
        are never fetched (gathers read scratch in their place) while
        its softmax still runs before the gate zeroes the output, so
        NaN in scratch K becomes NaN * 0 = NaN in the head gate and
        poisons every downstream activation.

        Quantized pools have no NaN to write — the reserved int8 code
        -128 is the position-granular sentinel instead: stage 3 decodes
        it to NaN (the same tripwire), while the derived scout views map
        it to 0 (finite scores, exactly like the fp32 pools' separate
        finite scout copies)."""
        from repro.core.quant import POISON_CODE
        from repro.models.attention import resolve_write_pages
        steps = jnp.arange(k, dtype=I32)
        stale = pos[:, None] + steps[None]                  # [B, k]
        reject = active[:, None] & (steps[None] >= n_commit[:, None])
        if self.paged:
            ps = self.pages.page_size
            ent = resolve_write_pages(stale, table_eff, ps, floors)
            reject = reject & (ent != 0)     # never poison the scratch page
            off = stale % ps
            kp = cache["k_pages"]                           # [L, P, N, ps, hd]
            poison = (jnp.asarray(POISON_CODE, kp.dtype)
                      if kp.dtype == jnp.int8
                      else jnp.asarray(jnp.nan, kp.dtype))
            cur = kp[:, ent, :, off]                        # [B, k, L, N, hd]
            val = jnp.where(reject[:, :, None, None, None], poison, cur)
            return {**cache, "k_pages": kp.at[:, ent, :, off].set(val)}
        kc = cache["k"]                                     # [L, B, S, N, hd]
        b = jnp.arange(kc.shape[1])[:, None]
        cur = kc[:, b, stale]                               # [L, B, k, N, hd]
        val = jnp.where(reject[None, :, :, None, None],
                        jnp.asarray(jnp.nan, cur.dtype), cur)
        return {**cache, "k": kc.at[:, b, stale].set(val)}

    def _spec_round(self, k, profile, params, tok, cache, table, floors,
                    pos, active, remaining, eos, inject):
        """One fused self-speculative round (``k`` = draft_len, static).

        Draft: ``k - 1`` sequential decode steps under the draft profile
        propose d_1..d_{k-1} (staged K/V writes ride the normal write
        path, floor-fenced). Verify: ONE ``k``-wide multi-query decode
        over [last_committed, d_1..d_{k-1}] re-scores every position with
        full fidelity — its exact K/V writes overwrite the draft staging
        — and yields the exact greedy token e_j per row. Accept: commit
        e_1..e_m where m-1 is the longest prefix with d_j == e_j; every
        committed token is an *exact* greedy token, so the output is
        token-identical to non-speculative decode at any acceptance rate.
        EOS and budget cut commits exactly like the fused horizon loop;
        rejected staged writes past the new frontier are NaN-poisoned.

        Emits (exact tokens [k, B], commit mask [k, B], fault mask [B],
        verify stats) + the updated carry — one host sync per round. A
        faulted row (non-finite verify logits, organic or injected)
        commits nothing, is parked like a done slot, and its staged
        writes are fully poisoned by the rollback fence (n_commit = 0).
        """
        table_eff = (None if table is None
                     else jnp.where(active[:, None], table, 0))

        if k > 1:
            def body(carry, _):
                tok_i, cache_i, pos_i = carry
                nxt, cache_i = self._draft_step(params, tok_i, cache_i,
                                                pos_i, table_eff, floors,
                                                profile)
                return (nxt, cache_i, pos_i + 1), nxt[:, 0]

            (_, cache, _), ds = jax.lax.scan(
                body, (tok, cache, pos), None, length=k - 1)
            drafts = jnp.moveaxis(ds, 0, 1)                 # [B, k-1]
        else:
            drafts = jnp.zeros((tok.shape[0], 0), I32)

        ver_in = jnp.concatenate([tok, drafts], axis=1)     # [B, k]
        steps = jnp.arange(k, dtype=I32)
        ver_pos = pos[:, None] + steps[None]                # [B, k]
        exact, bad, cache, stats = self._verify_step(
            params, ver_in, cache, ver_pos, table_eff, floors, inject)
        fault = active & bad

        # longest accepted prefix: drafts[:, j] proposed the token the
        # verify re-derived as exact[:, j]; the first mismatch still
        # commits the exact token (the "free" correction)
        lead = jnp.cumprod((drafts == exact[:, :k - 1]).astype(I32), axis=1)
        n_best = 1 + lead.sum(axis=1)                       # [B] in [1, k]
        within = steps[None] < n_best[:, None]
        is_eos = (eos[:, None] >= 0) & (exact == eos[:, None])
        cut = (is_eos & within).astype(I32)
        eos_before = jnp.cumsum(cut, axis=1) - cut          # EOS strictly before
        commit = (within & (eos_before == 0)
                  & (steps[None] < remaining[:, None]) & active[:, None]
                  & ~fault[:, None])
        n_commit = commit.sum(axis=1).astype(I32)

        cache = self._poison_rejected(cache, table_eff, floors, pos,
                                      n_commit, active, k)
        eos_hit = (is_eos & commit).any(axis=1)
        remaining = remaining - n_commit
        done = active & ~fault & (eos_hit | (remaining <= 0))
        new_active = active & ~done & ~fault
        last = jnp.take_along_axis(
            exact, jnp.maximum(n_commit - 1, 0)[:, None], axis=1)
        tok = jnp.where(new_active[:, None], last, 0)
        pos = jnp.where(new_active, pos + n_commit, 0)
        return ((exact.T, commit.T, fault, stats), tok, cache, pos,
                new_active, remaining)

    def _spec_round_paged_fn(self, k, profile, epoch, params, tok, cache,
                             table, floors, pos, active, remaining, eos,
                             inject):
        del epoch  # static retrace token only
        return self._spec_round(k, profile, params, tok, cache, table,
                                floors, pos, active, remaining, eos, inject)

    def _spec_round_dense_fn(self, k, profile, epoch, params, tok, cache,
                             pos, active, remaining, eos, inject):
        del epoch
        return self._spec_round(k, profile, params, tok, cache, None, None,
                                pos, active, remaining, eos, inject)

    # --------------------------------------------------------------- public
    def submit(self, req: Request, *, deadline_s: Optional[float] = None,
               max_queue_wait_s: Optional[float] = None) -> None:
        """Enqueue a request.

        ``deadline_s`` / ``max_queue_wait_s`` override the request's own
        fields (convenience for callers that build Requests elsewhere).
        Raises `QueueFull` when the stream scheduler's waiting queue is
        at ``SchedulerConfig.max_queue_depth`` — typed backpressure; the
        request is NOT enqueued and no Result is recorded for it."""
        if deadline_s is not None:
            req = dataclasses.replace(req, deadline_s=deadline_s)
        if max_queue_wait_s is not None:
            req = dataclasses.replace(req, max_queue_wait_s=max_queue_wait_s)
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt+generation exceeds max_len")
        if self.sched is not None:
            depth_max = self.sched.cfg.max_queue_depth
            if depth_max is not None and self.sched.depth >= depth_max:
                self.metrics["queue_rejected"] += 1
                raise QueueFull(
                    f"request {req.uid}: waiting queue at "
                    f"max_queue_depth={depth_max}; back off and resubmit")
        now = time.perf_counter()
        self._t_submit[req.uid] = now
        if req.deadline_s is not None or req.max_queue_wait_s is not None:
            self._deadlines[req.uid] = (
                now + req.deadline_s if req.deadline_s is not None else None,
                now + req.max_queue_wait_s
                if req.max_queue_wait_s is not None else None)
        if self.sched is not None:
            self.sched.enqueue(req)
        else:
            self._queue.append(req)

    def _bucket_for(self, n: int) -> int:
        if self.cfg.family in ("rwkv6", "zamba2"):
            # recurrent state: prefilling pad tokens would corrupt the
            # SSM state, so these families prefill at exact length
            return n
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_len

    @property
    def _can_chunk(self) -> bool:
        # chunked prefill needs an absolute-position embedding that can be
        # applied per chunk (rope) and a seq-indexed cache; with HDP active
        # the chunk boundary must also sit on a q-block boundary, or the
        # scout's per-block-row pooling shifts relative to one-shot prefill
        if self.cfg.family not in PAGEABLE_FAMILIES \
                or self.cfg.pos_emb != "rope":
            return False
        hdp = self.cfg.hdp
        if hdp is not None and hdp.enabled \
                and self.buckets[-1] % hdp.block_q:
            return False  # falls back to one-shot prefill at max_len
        return True

    # ------------------------------------------------------------ admission
    def _admit(self) -> None:
        n = min(len(self._queue), len(self._free))
        if n == 0:
            return
        take = [self._queue.pop(0) for _ in range(n)]
        groups: Dict[int, List[Request]] = {}
        long_reqs: List[Request] = []
        hits: List = []
        for req in take:
            plen = len(req.prompt)
            if self._can_chunk and plen > self.buckets[-1]:
                # long prompts prefill one at a time — defer their prefix
                # match so they can hit pages registered by *this* wave's
                # earlier requests (the shared-prompt burst case)
                long_reqs.append(req)
                continue
            shared = self._prefix_match(req) if self.prefix is not None \
                else None
            if shared:
                hits.append((req, shared))
            else:
                groups.setdefault(self._bucket_for(plen), []).append(req)
        jobs = []
        for bucket in sorted(groups):
            reqs = groups[bucket]
            for i in range(0, len(reqs), self.max_batch):
                jobs.append((bucket, reqs[i:i + self.max_batch]))
        # every work item is popped BEFORE it runs: a failing item unwinds
        # itself (requeue + ref release), the except arm below unwinds
        # only the never-started remainder — nothing is dropped, no match
        # ref is released twice
        try:
            while jobs:
                bucket, chunk = jobs.pop(0)
                self._prefill_group(bucket, chunk)
            while hits:
                req, shared = hits.pop(0)
                self._serve_hit(req, shared)
            while long_reqs:
                req = long_reqs.pop(0)
                shared = self._prefix_match(req) if self.prefix is not None \
                    else None
                if shared:
                    self._serve_hit(req, shared)
                else:
                    self._serve_cold(req)
        except BaseException:
            for _, chunk in jobs:
                self._queue[:0] = chunk
            for req, shared in hits:
                self.pages.allocator.unref(shared)
                self._queue.append(req)
            self._queue.extend(long_reqs)
            raise

    def _serve_hit(self, req: Request, shared: List[int]) -> None:
        """Serve a prefix-cache hit, unwinding cleanly on failure.

        Page reservation (the realistic failure: pool exhausted) happens
        up front. A reservation failure falls back to *cold* serving:
        the hit's own match refs can pin every evictable cached page, so
        releasing them and prefilling from scratch (which may now evict
        them) can succeed where the hit cannot — sharing is an
        optimization, never a reason to fail a request the cold path
        could serve. Any later pre-assignment failure releases the match
        refs and the reserved pages and requeues the request; once the
        slot owns the pages (``assigned``), slot teardown covers them."""
        full = len(shared) * self.pages.page_size == len(req.prompt)
        need = self._pages_for(req) - len(shared) + (1 if full else 0)
        try:
            fresh = self._reserve(need)
        except PoolExhausted:
            self.pages.allocator.unref(shared)
            self._serve_cold(req)
            return
        except BaseException:
            self.pages.allocator.unref(shared)
            self._queue.append(req)
            raise
        slot = self._free.pop(0)
        assigned = []
        try:
            if full:
                self._install_hit(req, shared, fresh, slot, assigned)
            else:
                self._prefill_suffix(req, shared, fresh, slot, assigned)
        except BaseException:
            if not assigned:
                self.pages.allocator.unref(shared + fresh)
                self._free.insert(0, slot)
                self._queue.append(req)
            elif req.uid not in self._results:
                # assigned but never activated: tear the slot down so
                # neither it nor its pages leak outside _active's reach
                self.pages.free(slot)
                self._free.insert(0, slot)
                self._queue.append(req)
            raise

    def _serve_cold(self, req: Request) -> None:
        """Prefill a request from scratch (no page sharing)."""
        plen = len(req.prompt)
        if self._can_chunk and plen > self.buckets[-1]:
            try:
                self._prefill_long(req)
            except BaseException:
                self._queue.append(req)
                raise
        else:
            self._prefill_group(self._bucket_for(plen), [req])

    def _prefix_match(self, req: Request) -> Optional[List[int]]:
        """Longest usable cached prefix of the prompt, as ref'd pages
        (page-granular, trimmed to HDP q-block alignment in the tree)."""
        return self.prefix.match(req.prompt, align=self._page_align) or None

    def _reserve(self, need: int) -> List[int]:
        """Allocate fresh pages, evicting LRU cached prefixes on pressure."""
        if self.faults is not None \
                and self.faults.pool_exhausted(self._cur_step):
            self.metrics["faults_injected"] += 1
            raise PoolExhausted(
                f"injected pool exhaustion (engine step {self._cur_step})")
        short = need - self.pages.allocator.available
        if short > 0 and self.prefix is not None:
            self.prefix.evict(short)
        return self.pages.allocator.alloc(need)

    def _pages_for(self, req: Request) -> int:
        return max(1, -(-(len(req.prompt) + req.max_new_tokens)
                        // self.pages.page_size))

    def _register_prefix(self, req: Request, slot: int) -> None:
        """Cache the slot's full prompt pages for future prefix hits.

        Only pages strictly before the decode write frontier (the resume
        rewrite at ``plen - 1``) are registered — a registered page is
        immutable from this moment on."""
        n_reg = (len(req.prompt) - 1) // self.pages.page_size
        if n_reg > 0:
            self.prefix.insert(req.prompt[:n_reg * self.pages.page_size],
                               self.pages.slot_pages(slot)[:n_reg])

    def _prefill_group(self, bucket: int, reqs: List[Request]) -> None:
        """One jitted prefill over same-bucket requests, stacked, fused
        with the cache scatter (the pool / slot cache is donated to it).

        The batch is stacked at exact size: the jit cache stays bounded by
        max_batch entries per bucket, and no duplicated padding row skews
        the recorded HDP stats."""
        nb = len(reqs)
        toks = np.zeros((nb, bucket), np.int32)
        for r, req in enumerate(reqs):
            plen = len(req.prompt)
            toks[r, :plen] = np.asarray(req.prompt, np.int32)
            # right-pad with the last token (positions beyond plen are
            # causally invisible to real rows and overwritten during
            # decode before they are ever attended)
            toks[r, plen:] = toks[r, plen - 1]
        slots = [self._free.pop(0) for _ in reqs]
        if self.paged:
            page_idx = np.zeros((nb, self.pages.pages_per_slot), np.int32)
            try:
                for r, (req, slot) in enumerate(zip(reqs, slots)):
                    pages = self._reserve(self._pages_for(req))
                    self.pages.assign(slot, pages)
                    page_idx[r, :len(pages)] = pages
            except BaseException:
                # pool exhausted mid-group: release what was assigned and
                # put slots + requests back — nothing leaks, nothing drops
                for slot in slots:
                    self.pages.free(slot)
                self._free[:0] = slots
                self._queue[:0] = reqs
                raise
            store, scatter = self.pages, jnp.asarray(page_idx)
        else:
            store, scatter = self.slots, jnp.asarray(slots, I32)
        with self.spans.span("engine.prefill") as sp:
            cache = store.take()                   # donated to the jit below
            try:
                with self._mesh_ctx():
                    new_cache, stats = self._prefill_jit(
                        self.params, jnp.asarray(toks), bucket, self._attn_epoch,
                        cache, scatter)
            except BaseException:
                store.restore_if_undonated(cache)
                for slot in slots:                 # roll admission back
                    if self.paged:
                        self.pages.free(slot)
                self._free[:0] = slots
                self._queue[:0] = reqs
                raise
            store.put(new_cache)
            self._record_stats(stats)
        self.metrics["prefill_calls"] += 1
        # padded forward size — the prefill-FLOPs proxy the prefix-cache
        # A/B asserts on (wall time is too load-sensitive for CI)
        self.metrics["prefill_tokens"] += nb * bucket
        self.metrics["prefill_prompt_tokens"] += sum(len(r.prompt)
                                                     for r in reqs)
        for r, (req, slot) in enumerate(zip(reqs, slots)):
            self._activate(req, slot, sp.s / nb)
            if self.prefix is not None:
                self._register_prefix(req, slot)

    def _tail_len(self, rem: int, off: int) -> int:
        for b in self.buckets:
            if b >= rem and off + b <= self.max_len:
                return b
        return rem  # exact-length fallback (one compile per distinct rem)

    def _chunk_step(self, prompt: np.ndarray, cache, off: int):
        """One `_chunk_jit` call at position ``off``; returns the updated
        (cache, off). The unit the stream scheduler's interleaved prefill
        advances by — decode runs between consecutive calls there."""
        plen = len(prompt)
        chunk = self.buckets[-1]
        rem = plen - off
        clen = chunk if rem >= chunk else self._tail_len(rem, off)
        piece = np.full((1, clen), prompt[plen - 1], np.int32)
        piece[0, :min(rem, clen)] = prompt[off:off + clen]
        with self._mesh_ctx():
            cache, stats = self._chunk_jit(
                self.params, jnp.asarray(piece), self._attn_epoch, cache,
                jnp.asarray(off, I32))
        self._record_stats(stats)
        self.metrics["prefill_tokens"] += clen
        self.metrics["prefill_prompt_tokens"] += min(rem, clen)
        return cache, off + clen

    def _chunk_loop(self, prompt: np.ndarray, cache, off: int):
        """Drive `_chunk_jit` from position `off` to the end of `prompt`."""
        while off < len(prompt):
            cache, off = self._chunk_step(prompt, cache, off)
        return cache

    def _prefill_long(self, req: Request) -> None:
        """Chunked prefill: bucket-sized chunks appended at a pos offset.

        Exactly equivalent to one-shot prefill except for HDP's early head
        gate, which applies per forward call: with tau_h > 0 each chunk
        gates on its own theta_head rather than the whole prompt's (all
        registered configs serve with tau_h = 0, where the paths are
        token-identical — pinned in tests/test_paged_cache.py)."""
        prompt = np.asarray(req.prompt, np.int32)
        with self.spans.span("engine.prefill") as sp:
            cache = registry.init_cache(self.cfg, 1, max_len=self.max_len)
            cache = self._chunk_loop(prompt, cache, 0)
        self.metrics["prefill_calls"] += 1
        self._install(req, cache, 0, sp.s)

    # ------------------------------------------------- interleaved prefill
    def _begin_stream_prefill(self, req: Request) -> Dict[str, Any]:
        """Open an incremental chunked prefill for the stream scheduler.

        The slot AND the request's full page footprint are reserved up
        front, so a begun prefill can always complete — later pool
        pressure defers *other* admissions, it can never strand a
        half-prefilled prompt. The returned state dict is advanced by
        `_advance_stream_prefill` one token-budget slice per engine
        step, with decode running in between."""
        pages = self._reserve(self._pages_for(req)) if self.paged else []
        slot = self._free.pop(0)
        return {"req": req, "slot": slot, "pages": pages,
                "prompt": np.asarray(req.prompt, np.int32),
                "cache": registry.init_cache(self.cfg, 1,
                                             max_len=self.max_len),
                "off": 0, "spent": 0.0}

    def _advance_stream_prefill(self, st: Dict[str, Any],
                                budget: int) -> bool:
        """Advance an interleaved prefill by >= 1 chunk, up to ``budget``
        prompt tokens; install + activate on completion (returns True).
        The chunk jit and install path are the exact ones `_prefill_long`
        drives in one blocking loop, so the resulting tokens are
        identical — only the pacing differs."""
        prompt = st["prompt"]
        plen = len(prompt)
        with self.spans.span("engine.prefill") as sp:
            done = 0
            while st["off"] < plen and done < budget:
                off0 = st["off"]
                st["cache"], st["off"] = self._chunk_step(
                    prompt, st["cache"], off0)
                done += st["off"] - off0
                self.metrics["sched_chunk_tokens"] += st["off"] - off0
        st["spent"] += sp.s
        if st["off"] < plen:
            return False
        self.metrics["prefill_calls"] += 1
        req, slot = st["req"], st["slot"]
        try:
            if self.paged:
                self.pages.assign(slot, st["pages"])
                st["pages"] = []           # owned by the slot from here
                self.pages.insert(st["cache"], slot, 0)
            else:
                self.slots.insert(st["cache"], slot, 0)
            self._activate(req, slot, st["spent"])
        except BaseException:
            # roll the slot back; _abort_stream_prefill (the scheduler's
            # unwind) returns it and any still-held pages, and requeues
            if self.paged and self.pages.slot_pages(slot):
                self.pages.free(slot)
            self._active.pop(slot, None)
            raise
        st["installed"] = True
        if self.paged and self.prefix is not None:
            self._register_prefix(req, slot)
        return True

    def _abort_stream_prefill(self, st: Dict[str, Any]) -> None:
        """Unwind a failed interleaved prefill: pages and slot return to
        their pools (a prefill that got as far as activation keeps its
        slot — the live request owns the teardown from there)."""
        if st.get("installed"):
            return
        if self.paged and st["pages"]:
            self.pages.allocator.unref(st["pages"])
        self._free.insert(0, st["slot"])

    def _pages_capacity(self) -> int:
        """Pages an admission could obtain right now: the free list plus
        everything LRU eviction could reclaim from the prefix cache —
        the supply side of the scheduler's token-budget check."""
        cap = self.pages.allocator.available
        if self.prefix is not None:
            cap += self.prefix.evictable_pages()
        return cap

    def _prefill_suffix(self, req: Request, shared: List[int],
                        fresh: List[int], slot: int,
                        assigned: List[int]) -> None:
        """Prefix-cache hit: share the matched pages, prefill the suffix.

        The request cache is seeded with the shared pages' K/V (a gather,
        no recompute), the suffix runs through the same chunked-prefill
        jit at offset ``m``, and only suffix/generation pages are fresh —
        the shared span of the insert scatter is scratch-redirected."""
        m = len(shared) * self.pages.page_size
        prompt = np.asarray(req.prompt, np.int32)
        with self.spans.span("engine.prefill") as sp:
            cache = self.pages.gather_prefix(shared)
            cache = self._chunk_loop(prompt, cache, m)
        self.metrics["prefill_calls"] += 1
        self.pages.assign(slot, shared + fresh, first_owned=len(shared))
        assigned.append(slot)              # slot owns every page from here
        self.pages.insert(cache, slot, 0, first_page=len(shared))
        self._activate(req, slot, sp.s, floor=len(shared))
        self._register_prefix(req, slot)

    def _install_hit(self, req: Request, shared: List[int],
                     fresh: List[int], slot: int,
                     assigned: List[int]) -> None:
        """Full-prompt hit: no prefill at all — every prompt page is
        already resident. The decode resume rewrites the last prompt
        position, which sits in the final shared page: that page is
        copy-on-write duplicated into a slot-owned page first, so the
        shared original stays immutable for its other readers."""
        self.pages.cow(shared[-1], fresh[0])
        self.metrics["cow_copies"] += 1
        pages = shared[:-1] + [fresh[0]] + fresh[1:]
        self.pages.assign(slot, pages, first_owned=len(shared) - 1)
        assigned.append(slot)              # slot owns every page from here
        self.pages.allocator.unref([shared[-1]])   # COW'd out of the slot
        self._activate(req, slot, 0.0, floor=len(shared) - 1)

    def _install(self, req: Request, one_cache, row: int,
                 prefill_s: float) -> None:
        if self.paged:
            pages = self._reserve(self._pages_for(req))  # fallible: first
        slot = self._free.pop(0)
        try:
            if self.paged:
                self.pages.assign(slot, pages)
                self.pages.insert(one_cache, slot, row)
            else:
                self.slots.insert(one_cache, slot, row)
            self._activate(req, slot, prefill_s)
        except BaseException:
            # roll the slot back (requeueing is the caller's job): pages
            # return via the slot if assigned, directly otherwise
            if self.paged:
                if self.pages.slot_pages(slot):
                    self.pages.free(slot)
                else:
                    self.pages.allocator.unref(pages)
            self._active.pop(slot, None)
            self._free.insert(0, slot)
            raise
        if self.paged and self.prefix is not None:
            self._register_prefix(req, slot)

    def _activate(self, req: Request, slot: int, prefill_s: float,
                  floor: int = 0) -> None:
        """Arm a slot's host + device decode state for an installed request.

        Uniform resume: the first decode step replays the last prompt
        token at its own position (its K/V rewrite is idempotent, and
        lands in a slot-owned page — `floor` fences the shared prefix)
        and yields the first generated token — identical for aligned,
        bucket-padded and prefix-shared prompts."""
        with self.spans.span("engine.activate"):
            plen = len(req.prompt)
            self._active[slot] = {"req": req, "generated": [],
                                  "act_seq": self._act_seq}
            self._act_seq += 1
            # prompt_len reports the ORIGINAL submission's prompt (restore
            # resumes fold generated tokens into req.prompt)
            res = Result(req.uid, req.orig_prompt_len or plen, [],
                         prefill_s=prefill_s, preemptions=req.preemptions)
            t_sub = self._t_submit.get(req.uid)
            if t_sub is not None:
                res.queue_wait_s = time.perf_counter() - t_sub
            self._results[req.uid] = res
            self._last_tok = self._last_tok.at[slot, 0].set(int(req.prompt[-1]))
            self._pos = self._pos.at[slot].set(plen - 1)
            self._active_dev = self._active_dev.at[slot].set(True)
            self._remaining_dev = self._remaining_dev.at[slot].set(
                req.max_new_tokens)
            self._eos_dev = self._eos_dev.at[slot].set(
                -1 if req.eos_id is None else req.eos_id)
            self._floor_dev = self._floor_dev.at[slot].set(floor)

    # -------------------------------------------------------------- metrics
    @staticmethod
    def _fresh_metrics() -> Dict[str, float]:
        return {"prefill_calls": 0, "prefill_tokens": 0,
                "prefill_prompt_tokens": 0, "decode_steps": 0,
                "tokens_out": 0,
                "block_sparsity": 0.0, "head_sparsity": 0.0,
                "page_sparsity": 0.0, "stat_samples": 0, "page_samples": 0,
                "cow_copies": 0, "spec_rounds": 0, "draft_tokens": 0,
                "accepted_tokens": 0,
                # stream-scheduler counters (zero when it is off)
                "sched_admitted": 0, "sched_recycled": 0,
                "sched_deferred": 0, "sched_chunk_tokens": 0,
                "sched_interleaved_steps": 0, "queue_depth_sum": 0,
                "queue_depth_samples": 0, "queue_depth_peak": 0,
                # fault-tolerance counters
                "sched_preempted": 0, "watchdog_shed": 0,
                "queue_rejected": 0, "faults_injected": 0,
                "req_cancelled": 0, "req_deadline": 0, "req_errors": 0}

    def reset_metrics(self) -> None:
        """Zero the aggregated serving metrics and the span totals (e.g.
        after a warmup pass, so reported throughput is steady-state rather
        than compile time)."""
        self.metrics = self._fresh_metrics()
        self.spans.reset()

    @staticmethod
    def _masked_mean(x, mask) -> float:
        """Mean over real samples: per-slot decode leaves are [L, B] and
        the active mask drops parked slots; prefill leaves ([L] scalars
        per layer, exact-size stacking — every row real) pass through."""
        x = np.asarray(x)
        if mask is not None and x.ndim >= 2 and x.shape[-1] == len(mask):
            x = x[..., mask]
        return float(np.mean(x))

    def _record_stats(self, stats, mask=None) -> None:
        """Accumulate one AttnStats sample (leaves carry a layer dim).

        ``mask`` [B] bool selects the slots that really decoded this
        step — parked slots run masked inside the fused loop and must
        not dilute the batchwise sparsity means."""
        if not self.collect_stats or stats is None:
            return
        # engine.stats: the observer's own cost, which the benchmark takes
        # out of the host loop's time; a prefill's sample waits here for
        # its program to finish
        with self.spans.span("engine.stats"):
            self._add_stats(stats, mask)

    def _add_stats(self, stats, mask) -> None:
        if mask is not None and not mask.any():
            return
        bs = getattr(stats, "block_sparsity", None)
        hs = getattr(stats, "head_sparsity", None)
        if bs is None or hs is None:
            return
        m = self.metrics
        # np.mean works on device and host leaves alike — the fused decode
        # loop hands this numpy slices it already fetched in its one sync
        b_mean = self._masked_mean(bs, mask)
        h_mean = self._masked_mean(hs, mask)
        m["block_sparsity"] += b_mean
        m["head_sparsity"] += h_mean
        if getattr(stats, "page_sparsity", None) is not None:
            # decode-only field: averaged over its own sample count so
            # prefill records don't dilute it
            p_mean = self._masked_mean(stats.page_sparsity, mask)
            m["page_sparsity"] += p_mean
            m["page_samples"] += 1
            if self.tuner is not None:
                # sharpen the cost model's sparse terms with measured
                # decode sparsity (prefill samples carry no page field
                # and would skew the decode-centric EMA)
                self.tuner.observe_sparsity(b_mean, h_mean, p_mean)
        m["stat_samples"] += 1

    def _finish(self, slot: int, now: Optional[float] = None, *,
                status: str = "ok", error: Optional[str] = None) -> None:
        st = self._active.pop(slot)
        req = st["req"]
        res = self._results[req.uid]
        # tokens generated before a preempt/failover restore come first:
        # the restore folded them into the prompt, so the concatenation is
        # byte-identical to an uninterrupted run
        res.tokens = list(req.prior_tokens) + st["generated"]
        res.decode_steps = len(res.tokens)
        res.complete = status == "ok"   # may have been marked incomplete by
        # a prior budget-exhausted run() whose follow-up finished the request
        res.status = status
        res.error = error
        res.preemptions = req.preemptions
        if status != "ok":
            self._count_status(status)
        t_sub = self._t_submit.pop(req.uid, None)
        self._deadlines.pop(req.uid, None)
        t_first = st.get("t_first")
        if t_sub is not None and t_first is not None:
            res.ttft_s = t_first - t_sub
        if now is not None and t_first is not None and len(res.tokens) > 1:
            res.tpot_s = (now - t_first) / (len(res.tokens) - 1)
        self._finished.append(req.uid)
        self._park_slot(slot)

    def _park_slot(self, slot: int) -> None:
        """Release a slot's cache state and return it to the free pool."""
        if self.paged:
            # unref, not free: pages the prefix cache still holds (and
            # pages shared into other live slots) survive the slot
            self.pages.free(slot)
        else:
            self.slots.clear(slot)
        # park the slot on position 0 / token 0: an inactive paged slot's
        # decode writes land in the scratch page via its zeroed table row
        self._pos = self._pos.at[slot].set(0)
        self._last_tok = self._last_tok.at[slot, 0].set(0)
        self._active_dev = self._active_dev.at[slot].set(False)
        self._remaining_dev = self._remaining_dev.at[slot].set(0)
        self._floor_dev = self._floor_dev.at[slot].set(0)
        self._free.append(slot)

    def _count_status(self, status: str) -> None:
        key = {"cancelled": "req_cancelled", "deadline": "req_deadline"} \
            .get(status, "req_errors")
        self.metrics[key] += 1

    # --------------------------------------------------- request lifecycle
    def _fail_request(self, req: Request, *, status: str,
                      error: Optional[str] = None) -> None:
        """Finish a request that never reached (or no longer holds) a
        slot with a typed non-"ok" Result; tokens generated before a
        preempt/failover restore are preserved."""
        res = Result(req.uid, req.orig_prompt_len or len(req.prompt),
                     list(req.prior_tokens), complete=False, status=status,
                     error=error, preemptions=req.preemptions)
        res.decode_steps = len(res.tokens)
        t_sub = self._t_submit.pop(req.uid, None)
        if t_sub is not None:
            res.queue_wait_s = time.perf_counter() - t_sub
        self._deadlines.pop(req.uid, None)
        self._results[req.uid] = res
        self._finished.append(req.uid)
        self._count_status(status)

    def cancel(self, uid: int, *, status: str = "cancelled",
               error: Optional[str] = None) -> bool:
        """Abort a request wherever it currently is — decoding in a slot,
        mid-interleaved-prefill, or queued — unwinding pages/slot/radix
        refs and recording a typed ``Result(status=...)``. Returns True
        when the request was found (False: unknown or already finished).
        """
        for slot, st in list(self._active.items()):
            if st["req"].uid == uid:
                self._finish(slot, time.perf_counter(), status=status,
                             error=error)
                return True
        for req in list(self._queue):
            if req.uid == uid:
                self._queue.remove(req)
                self._fail_request(req, status=status, error=error)
                return True
        if self.sched is not None:
            req = self.sched.cancel(uid)
            if req is not None:
                self._fail_request(req, status=status, error=error)
                return True
        return False

    def _enforce_deadlines(self) -> None:
        """Cancel expired requests (checked once at the top of every
        step — deadline granularity is the engine step, matching the
        one-host-sync-per-horizon design)."""
        if not self._deadlines:
            return
        now = time.perf_counter()
        active_uids = {st["req"].uid for st in self._active.values()}
        for uid, (dl, qdl) in list(self._deadlines.items()):
            if dl is not None and now >= dl:
                self.cancel(uid, status="deadline",
                            error=f"deadline_s exceeded after {now - dl:.3f}s")
            elif qdl is not None and now >= qdl and uid not in active_uids:
                self.cancel(uid, status="deadline",
                            error="max_queue_wait_s exceeded before "
                                  "activation")

    # ---------------------------------------------------- preempt/restore
    @staticmethod
    def _make_resume(req: Request, generated: List[int]) -> Request:
        """Recompute-resume continuation of a running request: generated
        tokens extend the prompt, budget shrinks to match. Greedy decode
        plus the chunked-prefill equivalence make re-serving this request
        byte-identical to never having interrupted it."""
        return dataclasses.replace(
            req,
            prompt=list(req.prompt) + list(generated),
            max_new_tokens=req.max_new_tokens - len(generated),
            prior_tokens=tuple(req.prior_tokens) + tuple(generated),
            orig_prompt_len=req.orig_prompt_len or len(req.prompt),
            preemptions=req.preemptions + 1)

    def _preempt_victim(self, max_priority: int) -> Optional[int]:
        """Slot of the best preemption victim: lowest priority strictly
        below ``max_priority``, newest activation among ties (least sunk
        decode work). None when nothing outranks — equal priorities never
        preempt each other, so the default (all zero) cannot livelock."""
        cands = [(st["req"].priority, -st["act_seq"], slot)
                 for slot, st in self._active.items()
                 if st["req"].priority < max_priority]
        return min(cands)[2] if cands else None

    def _preempt(self, slot: int) -> Request:
        """Tear a running slot down (pages freed, slot recycled, device
        state parked) and return its recompute-resume Request. The
        request's Result shell stays registered — re-activation on
        resume overwrites it."""
        st = self._active.pop(slot)
        resume = self._make_resume(st["req"], st["generated"])
        self._park_slot(slot)
        self.metrics["sched_preempted"] += 1
        return resume

    def _maybe_retune(self) -> None:
        """Flush pending tuner probes (host side, between device steps).

        A measured winner that flips a standing cost decision bumps the
        attention epoch — a static argument of the decode/spec AND
        prefill/chunk jits — so exactly the affected programs re-trace
        once and re-consult the tuner. Called at the top of every step
        and by the stream scheduler when a recycled slot re-enters the
        batch. No-op under static policy."""
        if self.tuner is not None and self.tuner.flush_probes():
            self._attn_epoch += 1

    def step(self) -> int:
        """One engine iteration: admit + one fused decode horizon (or,
        with ``spec_decode``, one fused self-speculative round).

        Generates up to ``horizon`` (``draft_len``) tokens per active
        slot in a single jitted call (one host sync per horizon/round);
        the serving cache is donated to the call, so page-pool updates
        are in place rather than a fresh copy per step. Returns the
        number of active slots stepped.

        With the stream scheduler, admission is one scheduler tick
        instead (budget check, ordering, interleaved prefill advance) and
        the tick's progress feeds the stall watchdog; decode itself
        always progresses (every active slot commits >= 1 token per
        horizon/round), so the watchdog can only trip while the batch is
        empty with requests stuck waiting."""
        try:
            with self.spans.span("engine.step"):
                return self._step_inner(self._cur_step)
        finally:
            # one increment per step() call, raise or return — the fault
            # injector keys every hook off this counter, and _reserve
            # reads it mid-step, so it must hold still within a step
            self._cur_step += 1

    def _inject_mask(self, step_no: int):
        """[B] bool mask of slots whose logits this step poisons (the
        NaN-tripwire fault hook); the shared all-False array on the fast
        path so the jit sees one constant donor-safe operand."""
        if self.faults is None:
            return self._zero_inject
        by_uid = {st["req"].uid: slot for slot, st in self._active.items()}
        uids = self.faults.nan_uids(step_no, by_uid)
        if not uids:
            return self._zero_inject
        mask = np.zeros(self.max_batch, bool)
        for u in uids:
            mask[by_uid[u]] = True
        self.metrics["faults_injected"] += len(uids)
        return jnp.asarray(mask)

    def _step_inner(self, step_no: int) -> int:
        if self.faults is not None:
            self.faults.sleep(step_no)
        self._enforce_deadlines()
        self._maybe_retune()
        with self.spans.span("engine.admit"):
            if self.sched is not None:
                ticked = self.sched.tick()
                self._sample_queue_depth()
            else:
                self._admit()
        if not self._active:
            if self.sched is not None:
                self.sched.watchdog(ticked)
            return 0
        n_stepped = len(self._active)
        if self.spec:
            return self._spec_step(n_stepped, step_no)
        # never scan past the longest remaining budget: the tail of the
        # horizon would provably have no active slot (EOS can still empty
        # a horizon early — those steps run masked and are not recorded)
        rem_max = max(st["req"].max_new_tokens - len(st["generated"])
                      for st in self._active.values())
        length = min(self.horizon, rem_max)

        inject = self._inject_mask(step_no)
        # engine.decode is decode_s: dispatch, then the horizon's single
        # host sync (tokens, active masks and the tiny per-step stats
        # leaves in one device_get), which also waits out every prefill
        # still queued on the device ahead of this decode
        with self.spans.span("engine.decode"):
            with self.spans.span("engine.decode.dispatch"):
                store = self.pages if self.paged else self.slots
                cache = store.take()               # donated to the jit below
                try:
                    if self.faults is not None:
                        # the harshest crash point: the donated handle is already
                        # taken, so the unwind below must restore it or the engine
                        # dies of DonatedCacheError on the next step
                        self.faults.step_error(step_no)
                    if self.paged:
                        with self._mesh_ctx():
                            ys, tok, new_cache, pos, active, remaining = \
                                self._decode_jit(
                                    length, self._attn_epoch, self.params,
                                    self._last_tok, cache, self.pages.table(),
                                    self._floor_dev, self._pos, self._active_dev,
                                    self._remaining_dev, self._eos_dev, inject)
                    else:
                        ys, tok, new_cache, pos, active, remaining = self._decode_jit(
                            length, self._attn_epoch, self.params, self._last_tok,
                            cache, self._pos, self._active_dev, self._remaining_dev,
                            self._eos_dev, inject)
                except BaseException:
                    # trace/compile failures leave the donated input untouched —
                    # restore the handle so the engine stays usable and the real
                    # error surfaces instead of a later DonatedCacheError
                    store.restore_if_undonated(cache)
                    raise
                store.put(new_cache)
            with self.spans.span("engine.decode.wait"):
                toks_np, act_np, fault_np, stats_np = jax.device_get(ys)
        t_sync = time.perf_counter()
        any_act = act_np.any(axis=1)
        ran = int(any_act.sum())                   # steps with any active slot
        self.metrics["decode_steps"] += ran
        self._last_tok = tok
        self._pos = pos
        self._active_dev = active
        self._remaining_dev = remaining
        if self.collect_stats and stats_np is not None:
            for t in range(ran):
                self._record_stats(jax.tree.map(lambda x: x[t], stats_np),
                                   mask=act_np[t])

        with self.spans.span("engine.drain"):
            for t in range(length):
                if not any_act[t]:
                    break
                for slot in list(self._active):
                    if not act_np[t, slot]:
                        continue
                    if fault_np[t, slot]:
                        # tripwire: this slot's logits went non-finite — its
                        # emitted token is garbage; abort just this request
                        self._finish(slot, t_sync, status="error",
                                     error="non-finite logits (per-slot "
                                           "NaN/poison tripwire)")
                        continue
                    st = self._active[slot]
                    req = st["req"]
                    tokn = int(toks_np[t, slot])
                    if not st["generated"]:
                        st["t_first"] = t_sync  # TTFT at sync granularity
                    st["generated"].append(tokn)
                    self.metrics["tokens_out"] += 1
                    done = (len(st["generated"]) >= req.max_new_tokens
                            or (req.eos_id is not None and tokn == req.eos_id))
                    if done:
                        self._finish(slot, t_sync)
            if self.sched is not None:
                self.sched.watchdog(True)  # decode progressed
        return n_stepped

    def _spec_step(self, n_stepped: int, step_no: int) -> int:
        """One fused speculative round: draft, verify, accept, rollback.

        Mirrors the horizon step's host side exactly — one device
        dispatch, one host sync, same drain loop — but the emitted mask
        is *commits* (accepted-and-exact tokens) rather than pre-step
        active flags. Commits are prefix runs per slot, so the drain can
        stop at the first all-parked step just like the horizon loop."""
        # never draft past the longest remaining budget: those proposals
        # could not be committed by ANY slot (the same clamp the horizon
        # loop applies to its scan length; at most draft_len distinct
        # compile entries exist per engine)
        rem_max = max(st["req"].max_new_tokens - len(st["generated"])
                      for st in self._active.values())
        if self.spec_ctl is not None:
            k_plan, profile = self.spec_ctl.plan()
            k = min(k_plan, rem_max)
        else:
            k, profile = min(self.draft_len, rem_max), self.draft_profile
        inject = self._inject_mask(step_no)
        with self.spans.span("engine.decode"):
            with self.spans.span("engine.decode.dispatch"):
                store = self.pages if self.paged else self.slots
                cache = store.take()               # donated to the jit below
                try:
                    if self.faults is not None:
                        self.faults.step_error(step_no)
                    if self.paged:
                        with self._mesh_ctx():
                            ys, tok, new_cache, pos, active, remaining = \
                                self._spec_jit(
                                    k, profile, self._attn_epoch, self.params,
                                    self._last_tok, cache, self.pages.table(),
                                    self._floor_dev, self._pos, self._active_dev,
                                    self._remaining_dev, self._eos_dev, inject)
                    else:
                        ys, tok, new_cache, pos, active, remaining = self._spec_jit(
                            k, profile, self._attn_epoch, self.params,
                            self._last_tok, cache, self._pos, self._active_dev,
                            self._remaining_dev, self._eos_dev, inject)
                except BaseException:
                    store.restore_if_undonated(cache)
                    raise
                store.put(new_cache)
            with self.spans.span("engine.decode.wait"):
                toks_np, com_np, fault_np, stats_np = jax.device_get(ys)
        t_sync = time.perf_counter()
        n_act = len(self._active)
        n_fault = int(fault_np.sum())
        self.metrics["spec_rounds"] += 1
        self.metrics["draft_tokens"] += (k - 1) * n_act
        # every non-faulted active slot commits >= 1 exact token per
        # round; commits beyond that first one are accepted draft
        # proposals. Parked slots ran masked and commit nothing, and a
        # faulted slot's commits are zeroed by the verify tripwire — they
        # never dilute the acceptance accounting.
        accepted = int(com_np.sum()) - (n_act - n_fault)
        self.metrics["accepted_tokens"] += accepted
        self.metrics["decode_steps"] += int(com_np.any(axis=1).sum())
        if self.spec_ctl is not None:
            self.spec_ctl.update(accepted, (k - 1) * n_act)
        self._last_tok = tok
        self._pos = pos
        self._active_dev = active
        self._remaining_dev = remaining
        if self.collect_stats and stats_np is not None:
            # one verify sample per round, masked to the slots that
            # actually decoded (com_np.any(0) == the pre-round active set)
            self._record_stats(stats_np, mask=com_np.any(axis=0))
        with self.spans.span("engine.drain"):
            for t in range(k):
                if not com_np[t].any():
                    break
                for slot in list(self._active):
                    if not com_np[t, slot]:
                        continue
                    st = self._active[slot]
                    req = st["req"]
                    tokn = int(toks_np[t, slot])
                    if not st["generated"]:
                        st["t_first"] = t_sync  # TTFT at sync granularity
                    st["generated"].append(tokn)
                    self.metrics["tokens_out"] += 1
                    done = (len(st["generated"]) >= req.max_new_tokens
                            or (req.eos_id is not None and tokn == req.eos_id))
                    if done:
                        self._finish(slot, t_sync)
            # faulted rows committed nothing this round (the tripwire fires at
            # verify, before any accept) — abort them after the commit drain
            for slot in list(self._active):
                if fault_np[slot]:
                    self._finish(slot, t_sync, status="error",
                                 error="non-finite logits (per-slot NaN/poison "
                                       "tripwire)")
            if self.sched is not None:
                self.sched.watchdog(True)  # decode progressed
        return n_stepped

    def _n_pending(self) -> int:
        """Requests not yet finished: active slots, the static queue, and
        (with the stream scheduler) its waiting + mid-prefill set."""
        n = len(self._queue) + len(self._active)
        if self.sched is not None:
            n += self.sched.depth
        return n

    def _pending_requests(self) -> List[Request]:
        reqs = list(self._queue)
        if self.sched is not None:
            reqs += self.sched.pending_requests()
        return reqs

    def _sample_queue_depth(self) -> None:
        """One per-step queue-depth sample (post-tick, so it reads the
        depth the step actually decodes under)."""
        d = self.sched.depth
        m = self.metrics
        m["queue_depth_sum"] += d
        m["queue_depth_samples"] += 1
        if d > m["queue_depth_peak"]:
            m["queue_depth_peak"] = d

    def run(self, max_steps: int = 10_000, *,
            strict: bool = False) -> Dict[int, Result]:
        """Drive until every submitted request completes.

        ``max_steps`` bounds engine iterations (decode horizons, not
        tokens). If the budget runs out with requests unfinished the
        affected Results are marked ``complete=False`` — active slots
        keep their partial tokens, queued requests get an empty Result —
        and a RuntimeWarning is emitted (or RuntimeError when
        ``strict=True``; engine state is left intact either way, so a
        further ``run()`` call can continue).
        """
        steps = 0
        while self._n_pending() and steps < max_steps:
            self.step()
            steps += 1
        if self._n_pending():
            waiting = self._pending_requests()
            msg = (f"Engine.run: step budget {max_steps} exhausted with "
                   f"{len(self._active)} active and {len(waiting)} "
                   f"queued request(s) unfinished")
            for st in self._active.values():
                res = self._results[st["req"].uid]
                res.tokens = list(st["generated"])
                res.decode_steps = len(res.tokens)
                res.complete = False
            for req in waiting:
                self._results[req.uid] = Result(
                    req.uid, len(req.prompt), [], complete=False)
            if strict:
                raise RuntimeError(msg)
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
        return dict(self._results)

    def serve(self, reqs: Optional[Sequence[Request]] = None, *,
              max_steps: int = 10_000):
        """Streaming serve loop: yields each Result as it completes.

        ``reqs`` are submitted up front (on top of anything already
        submitted); more requests may be submitted between yields — the
        loop keeps stepping until nothing is pending. Completion order
        is service order, not submission order, whenever the scheduler
        reorders admission or budgets differ. Raises RuntimeError when
        ``max_steps`` engine iterations pass without draining (the
        scheduler's watchdog usually fires first, naming the stuck
        requests)."""
        if reqs is not None:
            for r in reqs:
                self.submit(r)
        emitted = len(self._finished)   # don't re-yield pre-loop results
        steps = 0
        while self._n_pending():
            if steps >= max_steps:
                raise RuntimeError(
                    f"Engine.serve: step budget {max_steps} exhausted "
                    f"with {self._n_pending()} request(s) unfinished")
            self.step()
            steps += 1
            while emitted < len(self._finished):
                uid = self._finished[emitted]
                emitted += 1
                yield self._results[uid]

    def results(self) -> Dict[int, Result]:
        """Snapshot of every Result recorded so far (finished requests
        plus the still-active ones' shells)."""
        return dict(self._results)

    def resolved_backend(self, phase: str) -> str:
        """Name of the backend the registry resolves for a serving phase.

        ``phase``: "prefill" | "decode" | "draft" | "verify" (the last
        two are the speculative round's passes). Uses the SAME call
        constructor as ``attn_apply`` (models.attention.build_attn_call),
        so the report cannot drift from the dispatch. Under the cost
        policy the tuner's recorded decision for the phase (ground truth
        of what a trace actually dispatched) takes precedence; before
        any trace the static resolution is reported. Families without
        attention layers (recurrent) report "none".
        """
        if self.cfg.family in ("rwkv6",):
            return "none"
        decode_like = phase in ("decode", "draft", "verify")
        call = build_attn_call(
            self.cfg, mode="decode" if decode_like else "prefill",
            paged=self.paged and decode_like,
            per_slot=decode_like,
            collect_stats=self.collect_stats,
            draft=self.draft_profile if phase == "draft" else None,
            verify=phase == "verify")
        if self.tuner is not None:
            dec = self.tuner.decision_for(call)
            if dec is not None:
                return dec
        return resolve_backend(call, self.attn_spec).name

    # ------------------------------------------------------------- reporting
    def summary(self) -> Dict[str, float]:
        """The metrics, with the span totals (``spans``: name -> seconds
        and count) and what derives from them. ``prefill_s`` is host time
        in the ``engine.prefill`` spans: the time to dispatch the prefill
        programs (they run on the device asynchronously; with
        ``collect_stats`` their stats fetch waits for them). ``decode_s`` is
        host time in the ``engine.decode`` spans, dispatch to the end of
        the blocking fetch: the decode programs' device time plus that of
        every prefill queued on the device before them."""
        m = dict(self.metrics)
        m["prefill_s"] = self.spans.total("engine.prefill")
        m["decode_s"] = self.spans.total("engine.decode")
        m["spans"] = self.spans.totals()
        if m["decode_s"] > 0:
            m["decode_tok_s"] = m["tokens_out"] / m["decode_s"]
        if m["stat_samples"]:
            m["block_sparsity"] /= m["stat_samples"]
            m["head_sparsity"] /= m["stat_samples"]
        if m["page_samples"]:
            m["page_sparsity"] /= m["page_samples"]
        m["stream_sched"] = self.sched is not None
        if m.pop("queue_depth_samples") and self.sched is not None:
            m["queue_depth_mean"] = (m.pop("queue_depth_sum")
                                     / self.metrics["queue_depth_samples"])
        else:
            m.pop("queue_depth_sum", None)
        ttfts = sorted(r.ttft_s for r in self._results.values()
                       if r.ttft_s is not None)
        if ttfts:
            m["ttft_s_mean"] = float(np.mean(ttfts))
            m["ttft_s_p95"] = float(ttfts[int(0.95 * (len(ttfts) - 1))])
        tpots = [r.tpot_s for r in self._results.values()
                 if r.tpot_s is not None]
        if tpots:
            m["tpot_s_mean"] = float(np.mean(tpots))
        waits = [r.queue_wait_s for r in self._results.values()
                 if r.queue_wait_s is not None]
        if waits:
            m["queue_wait_s_mean"] = float(np.mean(waits))
        m["cache_backend"] = "paged" if self.paged else "dense"
        m["attn_backend_prefill"] = self.resolved_backend("prefill")
        m["attn_backend_decode"] = self.resolved_backend("decode")
        m["attn_policy"] = self.policy
        if m["decode_steps"]:
            m["meas_decode_step_s"] = m["decode_s"] / m["decode_steps"]
        if self.tuner is not None:
            ts = self.tuner.stats()
            m["tuner_hits"] = ts["hits"]
            m["tuner_misses"] = ts["misses"]
            m["tuner_probes"] = ts["probes"]
            m["tuner_cached"] = ts["measured"]
            est = None
            if self.cfg.family not in ("rwkv6",):
                # under spec decode the per-round hot path is the
                # multi-query verify call, not a plain decode step —
                # predict the phase that actually ran
                call = build_attn_call(
                    self.cfg, mode="decode", paged=self.paged,
                    per_slot=True, collect_stats=self.collect_stats,
                    verify=self.spec)
                est = self.tuner.estimate_for(call)
            if est is not None:
                from repro.autotune import predict_engine_step
                _, ce = est
                m["pred_decode_step_s"] = predict_engine_step(
                    registry.param_count(self.cfg, active_only=True),
                    self.max_batch, self.cfg.n_layers, ce, self.tuner.hw)
        if self.faults is not None:
            m["fault_plan"] = self.faults.plan.spec
            m["faults_fired"] = len(self.faults.fired)
        m["spec_decode"] = self.spec
        if self.spec:
            m["draft_len"] = self.draft_len
            m["acceptance_rate"] = (
                m["accepted_tokens"] / m["draft_tokens"]
                if m["draft_tokens"] else 0.0)
            m["attn_backend_draft"] = self.resolved_backend("draft")
            m["attn_backend_verify"] = self.resolved_backend("verify")
            m["adaptive_spec"] = self.spec_ctl is not None
            if self.spec_ctl is not None:
                sc = self.spec_ctl.summary()
                m["acceptance_ema"] = sc["acceptance_ema"]
                m["draft_len_mean"] = sc["draft_len_mean"]
                m["spec_plans"] = sc["rounds"]
        if self.paged:
            # resident bytes at the allocation high-water mark — what a
            # demand-sized pool must hold (the pool itself is max-sized
            # here for static shapes). With the prefix cache on, the peak
            # counts shared pages ONCE — the whole point of sharing.
            m["cache_bytes"] = self.pages.active_bytes(self.pages.peak_pages)
            m["cache_bytes_pool"] = self.pages.pool_bytes()
            m["kv_dtype"] = self.kv_dtype
            m["kv_scale"] = self.kv_scale
            m["tp"] = self.tp
            if self.mesh is not None:
                m["mesh_shape"] = dict(self.mesh.shape)
                m["cache_bytes_pool_per_shard"] = \
                    self.pages.pool_bytes_per_shard()
                # per decode step, per layer: each shard all-gathers the
                # other shards' per-head output slices before the
                # o-projection (the only cross-shard traffic)
                m["collective_bytes_per_layer"] = int(
                    self.max_batch * self.cfg.n_heads * self.cfg.hd * 4
                    * (self.tp - 1) / self.tp)
            m["cache_bytes_per_token"] = self.pages.bytes_per_token()
            m["pages_peak"] = self.pages.peak_pages
            m["pages_in_use"] = self.pages.pages_in_use
            m["page_size"] = self.pages.page_size
            m["prefix_cache"] = self.prefix is not None
            if self.prefix is not None:
                m["prefix_hits"] = self.prefix.hits
                m["prefix_misses"] = self.prefix.misses
                m["prefix_hit_tokens"] = self.prefix.hit_tokens
                m["prefix_evictions"] = self.prefix.evictions
                m["pages_cached"] = self.prefix.cached_pages
        else:
            m["cache_bytes"] = kv_cache.cache_bytes(self.slots.cache)
            m["kv_dtype"] = "fp32"
        return m
