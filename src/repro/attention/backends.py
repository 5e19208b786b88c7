"""The production backends, ported from the former ad-hoc entry points.

Seven implementations, one registry (reference lives in reference.py):

| backend            | ports                                        | calls it supports                    |
|--------------------|----------------------------------------------|--------------------------------------|
| xla_dense          | chunked/local/decode_attention               | HDP off (dense; paged decode)        |
| xla_hdp            | hdp_prefill/decode_attention                 | HDP on, dense layout (+draft/verify) |
| paged_hdp_decode   | hdp_paged_decode_attention (XLA stage 3)     | HDP on, paged decode (+draft/verify) |
| pallas_flash       | kernels.flash_attention                      | HDP off, aligned self-attn prefill   |
| pallas_hdp_block   | kernels.ops.hdp_attention_tpu                | HDP on, aligned dense prefill        |
| pallas_paged_decode| kernels.hdp_paged_decode (gather-free FUM)   | HDP on, causal paged (+verify)       |

Pallas backends rank above XLA only on TPU; off-TPU they run in
interpret mode when explicitly requested and are never auto-selected.
``pallas_paged_decode`` streams surviving pages straight from the pool,
so pruned pages cost no HBM traffic at all; every paged call it refuses
(non-causal, windowed, draft, approx-softmax, absmax-scaled) resolves to
``paged_hdp_decode``. None has a VJP, so none supports trainable calls,
and the FUM kernels' per-row validity (cols < kv_len) cannot express a
sliding window's lower bound — windowed calls fall back to the XLA chain.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.attention.reference import _densify
from repro.attention.registry import register_backend
from repro.attention.spec import AttnCall
from repro.attention.stats import normalize_stats
from repro.models import attention as A


def _heads(x, G):
    """[B,Sk,N,hd] -> [B,N*G,Sk,hd] (repeat KV heads across the group)."""
    return jnp.repeat(x.transpose(0, 2, 1, 3), G, axis=1)


# ------------------------------------------------------------------ xla_dense
def _supports_xla_dense(call: AttnCall) -> bool:
    return call.hdp is None


@register_backend("xla_dense", supports=_supports_xla_dense, priority=10,
                  tags=("xla",))
def run_xla_dense(q, k, v, call, *, q_pos, k_pos, cache=None, page_table=None):
    if call.layout == "paged":
        k, v, _ = _densify(cache, page_table)
    if call.mode == "decode":
        o = A.decode_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                               window=call.window, causal=call.causal)
    elif (call.window and q.shape[3] > call.window
          and k.shape[1] == q.shape[3]):
        # block-local path needs aligned q/k; chunked serving prefill
        # (q = one chunk, k = whole cache) windows via chunked_attention
        o = A.local_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                              window=call.window, causal=call.causal)
    else:
        chunk = call.chunk if call.chunk else k.shape[1]
        o = A.chunked_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                chunk=min(chunk, max(k.shape[1], 1)),
                                causal=call.causal, window=call.window)
    return o, None


# -------------------------------------------------------------------- xla_hdp
def _supports_xla_hdp(call: AttnCall) -> bool:
    return call.hdp is not None and call.layout == "dense"


@register_backend("xla_hdp", supports=_supports_xla_hdp, priority=10,
                  tags=("xla",))
def run_xla_hdp(q, k, v, call, *, q_pos, k_pos, cache=None, page_table=None):
    if call.mode == "decode":
        out, st = A.hdp_decode_attention(
            q, k, v, q_pos=q_pos, k_pos=k_pos, hdp=call.hdp,
            window=call.window, return_stats=call.needs_stats,
            draft=call.draft, per_query=call.verify)
    else:
        out, st = A.hdp_prefill_attention(
            q, k, v, q_pos=q_pos, k_pos=k_pos, hdp=call.hdp,
            window=call.window, return_stats=call.needs_stats)
    return out, normalize_stats(st)


# ----------------------------------------------------------- paged_hdp_decode
def _supports_paged_hdp(call: AttnCall) -> bool:
    return call.hdp is not None and call.layout == "paged"


def _run_paged(q, call, *, q_pos, k_pos, cache, page_table, stage3):
    # quantized pools carry no scout copies (k_scout is None; the scout
    # is a view of the int8 codes) and per-page scales instead
    out, st = A.hdp_paged_decode_attention(
        q, cache["k_pages"], cache["v_pages"], cache.get("k_scout"),
        page_table,
        q_pos=q_pos, k_pos=k_pos, hdp=call.hdp, window=call.window,
        return_stats=call.needs_stats, stage3=stage3,
        draft=call.draft, per_query=call.verify,
        fk_pool=cache.get("f_scout"),
        k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"),
        kv_scale=call.kv_scale,
        layer=cache.get("layer"))
    return out, normalize_stats(st)


@register_backend("paged_hdp_decode", supports=_supports_paged_hdp,
                  priority=10, tags=("xla",))
def run_paged_hdp_decode(q, k, v, call, *, q_pos, k_pos, cache=None,
                         page_table=None):
    return _run_paged(q, call, q_pos=q_pos, k_pos=k_pos, cache=cache,
                      page_table=page_table, stage3="xla")


# --------------------------------------------------------------- pallas_flash
def _supports_pallas_flash(call: AttnCall) -> bool:
    return (call.hdp is None and call.layout == "dense"
            and call.mode == "prefill" and call.self_aligned
            and not call.per_slot and not call.trainable
            and call.window == 0)


@register_backend("pallas_flash", supports=_supports_pallas_flash,
                  priority=5, tpu_priority=20, tags=("pallas",))
def run_pallas_flash(q, k, v, call, *, q_pos, k_pos, cache=None,
                     page_table=None):
    from repro.kernels.ops import flash
    B, N, G, Sq, hd = q.shape
    out = flash(q.reshape(B, N * G, Sq, hd), _heads(k, G), _heads(v, G),
                causal=call.causal)
    return out.reshape(B, N, G, Sq, hd), None


# ----------------------------------------------------------- pallas_hdp_block
def _supports_pallas_hdp(call: AttnCall) -> bool:
    if call.hdp is None or call.trainable or call.window != 0 \
            or call.hdp.approx_softmax:
        return False
    return (call.layout == "dense" and call.mode == "prefill"
            and call.self_aligned and not call.per_slot
            and call.hdp.causal == call.causal)


@register_backend("pallas_hdp_block", supports=_supports_pallas_hdp,
                  priority=5, tpu_priority=20, tags=("pallas",))
def run_pallas_hdp_block(q, k, v, call, *, q_pos, k_pos, cache=None,
                         page_table=None):
    from repro.kernels.ops import hdp_attention_tpu
    B, N, G, Sq, hd = q.shape
    out, st = hdp_attention_tpu(
        q.reshape(B, N * G, Sq, hd), _heads(k, G), _heads(v, G), call.hdp,
        return_stats=call.needs_stats)
    return out.reshape(B, N, G, Sq, hd), normalize_stats(st)


# --------------------------------------------------------- pallas_paged_decode
def _supports_pallas_paged(call: AttnCall) -> bool:
    """Gather-free FUM decode: page table drives the kernel's DMA directly.

    Needs the plain causal paged-decode shape: the kernel's per-row
    validity is ``cols < kv_len`` (upper bound only), which is exactly the
    causal mask of single-token decode — or of a multi-query verify call,
    whose consecutive rows each extend the bound by their query index —
    but cannot express a sliding window's lower bound or a non-causal
    extent. Draft calls fall down the chain: the kernel reads the
    full-precision pool, which the draft score sources never touch. So do
    calibrated (``absmax``) pools: the kernel derives its in-register
    scout from the static grid, which would fork the keep mask.
    """
    return (call.hdp is not None and call.layout == "paged"
            and call.mode == "decode" and not call.trainable
            and call.window == 0 and not call.hdp.approx_softmax
            and call.causal and call.hdp.causal and call.draft is None
            and call.kv_scale != "absmax")


@register_backend("pallas_paged_decode", supports=_supports_pallas_paged,
                  priority=6, tpu_priority=25, tags=("pallas",))
def run_pallas_paged_decode(q, k, v, call, *, q_pos, k_pos, cache=None,
                            page_table=None):
    return _run_paged(q, call, q_pos=q_pos, k_pos=k_pos, cache=cache,
                      page_table=page_table, stage3="pallas_paged")
