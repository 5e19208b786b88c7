"""Operations and bytes of the served model and of the paged decode
kernel, counted from shapes.

Model FLOPs are the model's own work, whatever implements it: 2 per
multiply-add of every weight matmul, plus attention over the full causal
(or windowed) context — QK^T and PV, 4 * context * heads * head_dim per
token and layer — with nothing subtracted for HDP's pruning. A prompt
token does not pay the LM head (the engine reads no logits at prefill);
a generated token does.
"""
from __future__ import annotations

from bench.weights import dims


def matmul_flops_per_token(config: dict, *, lm_head: bool) -> int:
    D = dims(config)
    d, H, N, hd, f = D["d"], D["H"], D["N"], D["hd"], D["f"]
    per_layer = d * (H + 2 * N) * hd + H * hd * d + 3 * d * f
    total = D["L"] * per_layer
    if lm_head:
        total += d * D["V"]
    return 2 * total


def attn_flops(config: dict, context: int) -> int:
    """QK^T and PV of one query over ``context`` keys, all layers."""
    D = dims(config)
    c = min(context, D["window"]) if D["window"] else context
    return 4 * c * D["H"] * D["hd"] * D["L"]


def decode_token_flops(config: dict, context: int) -> int:
    """One generated token whose query attends ``context`` keys."""
    return matmul_flops_per_token(config, lm_head=True) + attn_flops(config, context)


def prefill_flops(config: dict, prompt_len: int, start: int = 0) -> int:
    """Prompt positions ``start .. prompt_len - 1``: position p attends
    p + 1 keys (fewer under a window)."""
    D = dims(config)
    n = prompt_len - start
    mm = n * matmul_flops_per_token(config, lm_head=False)
    per_key = 4 * D["H"] * D["hd"] * D["L"]
    w = D["window"]
    if not w:
        keys = (start + 1 + prompt_len) * n // 2       # sum of p + 1
    else:
        keys = sum(min(p + 1, w) for p in range(start, prompt_len))
    return mm + per_key * keys


def paged_kernel_cost(config: dict, *, kept_pages: float, calls: int,
                      slots: int, table_pages: int) -> tuple:
    """(bytes, flops) of ``calls`` calls of the paged decode kernel on one
    chip of the configuration's ``deployment.chips``.

    One call is one layer of one decode step over ``slots`` slots: it
    DMAs, per slot and kv head, each kept page's int8 K and V tile
    (page_size x head_dim bytes each) and, past the kept count, the
    scratch page's tiles once; the query and output blocks (float32,
    query group x head_dim), one int32 keep entry per group row and table
    column, and the scalar-prefetched page lists. ``kept_pages`` is the
    total of kept pages over all calls and slots (each counted once per
    kv head by this function). FLOPs: QK^T, FQ FK^T and PV of the query
    group against each kept tile.

    Under tensor parallelism over the deployment's chips each chip's
    kernel walks only its own ``N // chips`` kv heads (``distribution/tp.py``
    shards the pool on its head axis), with every slot's page lists:
    ``calls`` are one chip's calls and the count is one chip's."""
    D = dims(config)
    dep = config["deployment"]
    ps, hd = int(dep["page_size"]), D["hd"]
    N = D["N"] // int(dep["chips"])
    G = D["H"] // D["N"]
    tile = ps * hd                                  # int8 bytes
    per_call_fixed = (slots * N * (2 * tile        # scratch K and V tile
                                   + 2 * G * hd * 4  # q and out blocks
                                   + table_pages * G * 4)  # keep entries
                      + slots * table_pages * 4 * 2)       # page lists
    bytes_ = kept_pages * N * 2 * tile + calls * per_call_fixed
    flops = kept_pages * N * 3 * 2 * G * ps * hd
    return float(bytes_), float(flops)


def roofline_s(bytes_: float, flops: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of the byte and the
    operation bound."""
    return max(bytes_ / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])
