"""Plain float32 reference of a served decoder: the comparison behind
``correct``.

Straightforward ``jax.numpy`` at the published widths, under
``jax.default_matmul_precision("highest")``, with nothing taken from the
program under test: the configuration file gives the sizes, and
``bench.weights`` makes the weights again from the seed. It computes, for
one prompt and the tokens the engine served for it, the logits at every
served position, so the caller can read how far each served token lies
below the reference's best. It computes on one device, whatever layout
the weights arrive in: the embedding, each layer's slice and the LM head
are moved to that device before they are used.

What it implements, beside the model (RMSNorm, RoPE, GQA attention,
SiLU-GLU MLP, the LM head, and each bias where the weights hold it, as
the published modelling code adds it: q/k/v after their projections,
Qwen2's and Llama's; o after the output projection, Llama's under
``attention_bias``; gate, up and down after theirs, Llama's under
``mlp_bias``; transformers 4.57 ``models/llama/modeling_llama.py``
:149-151 and :211-220, ``models/qwen2/modeling_qwen2.py``:134-137):

* the serving numerics the configuration states: K and V stored on the
  int8 pool grid (``round(x / step)`` clipped to +/-127 codes), queries
  snapped to the Q(int_bits).(frac_bits) fixed-point grid;
* HDP as arXiv:2407.12893 (Algorithm 2) defines it, in the serving
  engine's block geometry: integer scout ``trunc(q) . trunc(k)``, block
  importance theta = sum of |integer scores| over a (query block, key
  block) tile, row threshold ``min(rho * max + (1 - rho) * mean, max)``
  over causally valid blocks, blocks below it excluded from the softmax,
  the approximate score ``QK - FQ FK`` (the fraction-times-fraction term
  dropped), and the head gate ``theta_head / n_valid > tau_h``.

Departures from the paper, each the engine's documented adaptation for a
causal decoder, and so part of what is served:

* blocks are 128 x 128 (the paper's ASIC uses 2 x 2); future blocks take
  no part in the row statistics;
* prompt rows are pooled per 128-row query block, exactly as the engine
  prefills them: the prompt is right-padded with its last token to the
  engine's bucket, or prefilled in chunks of the largest bucket with the
  last chunk padded to a bucket, and the head gate is taken per chunk;
* each generated token's query is its own block row, its key blocks are
  the cache pages (position // 128), and its head gate uses the scores
  of that one row; the first generated token comes from a decode step at
  the prompt's last position, whose keys and values then replace the
  prompt pass's ones there;
* a sliding window (h2o-danube) masks keys ``window`` or more positions
  back in both passes.

``precision`` names the inputs of every weight matmul: ``"fp32"`` (the
reference), ``"bf16"``, or ``"fp8"`` (float8 e4m3, weights scaled per
output column and activations per row). The control is the reference one
step below the configuration's dtype: ``CONTROL[dtype]``.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import dims

F32 = jnp.float32
NEG = -1e30
QB = 128          # query rows per block (the engine's HDP block_q)
FP8_MAX = 448.0
#: the control's precision for each configuration dtype
CONTROL = {"bfloat16": "fp8", "float32": "bf16"}


# ------------------------------------------------------------ engine policy
def prefill_plan(plen: int, buckets: Sequence[int], max_len: int
                 ) -> Tuple[int, List[Tuple[int, int]]]:
    """How the engine prefills a prompt of ``plen`` tokens: the padded
    length and the (offset, length) chunks. A prompt up to the largest
    bucket pads to the smallest bucket that holds it; a longer one runs
    in chunks of the largest bucket, its last chunk padded to the
    smallest bucket that holds the rest and fits under ``max_len``."""
    buckets = sorted(buckets)
    big = buckets[-1]
    if plen <= big:
        b = next(b for b in buckets if b >= plen)
        return b, [(0, b)]
    chunks, off = [], 0
    while off < plen:
        rem = plen - off
        if rem >= big:
            clen = big
        else:
            clen = next((b for b in buckets if b >= rem and off + b <= max_len),
                        rem)
        chunks.append((off, clen))
        off += clen
    return off, chunks


# ------------------------------------------------------------------ maths
def _mm(a, w, precision: str):
    """a [..., k] @ w [k, n] in float32, or through bfloat16 / float8
    inputs."""
    a = a.astype(F32)
    w = w.astype(F32)
    if precision == "bf16":
        return a.astype(jnp.bfloat16).astype(F32) @ w.astype(jnp.bfloat16).astype(F32)
    if precision == "fp8":
        sa = jnp.maximum(jnp.abs(a).max(-1, keepdims=True), 1e-30) / FP8_MAX
        sw = jnp.maximum(jnp.abs(w).max(0, keepdims=True), 1e-30) / FP8_MAX
        a8 = (a / sa).astype(jnp.float8_e4m3fn).astype(F32)
        w8 = (w / sw).astype(jnp.float8_e4m3fn).astype(F32)
        return (a8 @ w8) * sa * sw
    return a @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w.astype(F32)


def _plus(y, bias):
    """``y`` plus ``bias`` in float32, where the weights hold one."""
    return y if bias is None else y + bias.astype(F32)


def post_attention(lp, x, o, eps, precision: str = "fp32"):
    """The rest of a layer once attention has given ``o`` [S, H * hd] for
    the residual stream ``x`` [S, d]: the output projection and its bias,
    the residual, RMSNorm, ``silu(h Wg + b_gate) * (h Wu + b_up)``, then
    ``. Wd + b_down`` and the residual (``lp``: one layer's weights)."""
    a, f = lp["attn"], lp["ffn"]
    d = x.shape[-1]
    x = x + _plus(_mm(o, a["wo"].reshape(-1, d), precision), a.get("bo"))
    h = _rms(x, lp["ln2"]["w"], eps)
    g = _plus(_mm(h, f["w_gate"], precision), f.get("b_gate"))
    u = _plus(_mm(h, f["w_up"], precision), f.get("b_up"))
    return x + _plus(_mm(jax.nn.silu(g) * u, f["w_down"], precision),
                     f.get("b_down"))


def _rope(x, pos, theta):
    """x [S, heads, hd], pos [S]: rotate-half RoPE."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _grid(x, step):
    """The int8 pool grid: codes round(x / step) clipped to +/-127."""
    return jnp.clip(jnp.round(x / step), -127, 127) * step


def _fixed(x, int_bits, frac_bits):
    s = 2.0 ** frac_bits
    return jnp.clip(jnp.round(x * s) / s, -(2.0 ** int_bits),
                    2.0 ** int_bits - 2.0 ** -frac_bits)


def _attend_block(qq, kq, v, qpos, kpos, hdp, window, bk):
    """One block of query rows against all keys, HDP-masked.

    qq [H, R, hd] fixed-grid queries, kq/v [N, S, hd] grid keys/values,
    qpos [R] / kpos [S] positions (-1: no row / no key). ``hdp["pool"]``
    says whether the R rows pool into one block row (a prompt's query
    block) or each row is its own (a decode step). Returns the ungated
    output [H, R, hd], the head-score sum [H, R'] and the valid count
    [R'] (R' = 1 pooled, R per row)."""
    H, R, hd = qq.shape
    N, S, _ = kq.shape
    G = H // N
    kq_h = jnp.repeat(kq, G, axis=0)
    v_h = jnp.repeat(v, G, axis=0)
    iq, ik = jnp.trunc(qq), jnp.trunc(kq_h)
    fq, fk = qq - iq, kq_h - ik
    valid = (qpos[:, None] >= 0) & (kpos[None, :] >= 0) \
        & (kpos[None, :] <= qpos[:, None])
    if window:
        valid &= (qpos[:, None] - kpos[None, :]) < window
    s_int = jnp.einsum("hrd,hsd->hrs", iq, ik)
    a = jnp.abs(jnp.where(valid[None], s_int, 0.0)).reshape(H, R, S // bk, bk)
    vb = valid.reshape(R, S // bk, bk)
    if hdp["pool"]:
        theta = a.sum(axis=(1, 3))[:, None, :]              # [H, 1, nb]
        bvalid = vb.any(axis=(0, 2))[None, None, :]         # [1, 1, nb]
        n_valid = valid.sum()[None].astype(F32)             # [1]
    else:
        theta = a.sum(axis=3)                               # [H, R, nb]
        bvalid = vb.any(axis=2)[None]                       # [1, R, nb]
        n_valid = valid.sum(-1).astype(F32)                 # [R]
    bv = jnp.broadcast_to(bvalid, theta.shape)
    cnt = jnp.maximum(bv.sum(-1, keepdims=True), 1)
    tmax = jnp.where(bv, theta, -jnp.inf).max(-1, keepdims=True)
    tmean = jnp.where(bv, theta, 0.0).sum(-1, keepdims=True) / cnt
    rho = hdp["rho_b"]
    thr = jnp.minimum(rho * tmax + (1 - rho) * tmean, tmax)
    keep = (theta >= thr) & bv                               # [H, R'|1, nb]
    head_sum = jnp.where(bv, theta, 0.0).sum(-1)            # [H, R']
    keep_e = jnp.repeat(keep, bk, axis=-1) & valid[None]     # [H, R, S]
    s = jnp.einsum("hrd,hsd->hrs", qq, kq_h)
    if hdp["approx"]:
        s = s - jnp.einsum("hrd,hsd->hrs", fq, fk)
    s = s / jnp.sqrt(jnp.asarray(hd, F32))
    s = jnp.where(keep_e, s, NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = jnp.where(keep_e, p, 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("hrs,hsd->hrd", p, v_h), head_sum, n_valid


@functools.partial(jax.jit, static_argnames=("static",))
def _layer(lp, x_pf, x_dec, pos_pf, pos_dec, chunk_of_block, plen, *, static):
    (D, hdp, precision, n_chunks, dtype) = static
    D, hdp = dict(D), dict(hdp)
    H, N, hd, eps = D["H"], D["N"], D["hd"], D["eps"]
    step, ib, fb = hdp["grid_step"], hdp["int_bits"], hdp["frac_bits"]
    window, bk = D["window"], hdp["block_k"]
    a = lp["attn"]

    def qkv(x, pos):
        h = _rms(x, lp["ln1"]["w"], eps)
        d = x.shape[-1]
        q = _mm(h, a["wq"].reshape(d, H * hd), precision).reshape(-1, H, hd)
        k = _mm(h, a["wk"].reshape(d, N * hd), precision).reshape(-1, N, hd)
        v = _mm(h, a["wv"].reshape(d, N * hd), precision).reshape(-1, N, hd)
        if "bq" in a:
            q, k, v = (q + a["bq"].astype(F32), k + a["bk"].astype(F32),
                       v + a["bv"].astype(F32))
        # rotated q and k held in the served dtype, where the scout reads them
        q = _rope(q, pos, D["rope_theta"]).astype(dtype).astype(F32)
        k = _rope(k, pos, D["rope_theta"]).astype(dtype).astype(F32)
        return (_fixed(q, ib, fb).transpose(1, 0, 2),
                _grid(k, step).transpose(1, 0, 2),
                _grid(v, step).transpose(1, 0, 2))

    def finish(x, o):
        o = o.transpose(1, 0, 2).reshape(-1, H * hd)
        return post_attention(lp, x, o, eps, precision)

    # ---- prompt rows: query blocks pooled, head gate per chunk
    q_pf, k_pf, v_pf = qkv(x_pf, pos_pf)
    S_pf = x_pf.shape[0]
    kpos_pf = pos_pf
    pool = {**hdp, "pool": True}

    def pf_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q_pf, i * QB, QB, axis=1)
        pb = jax.lax.dynamic_slice_in_dim(pos_pf, i * QB, QB)
        return _attend_block(qb, k_pf, v_pf, pb, kpos_pf, pool, window, bk)

    o, hs, nv = jax.lax.map(pf_block, jnp.arange(S_pf // QB))
    # o [nb, H, QB, hd]; hs [nb, H, 1]; nv [nb, 1]
    onehot = (chunk_of_block[:, None] == jnp.arange(n_chunks)[None]).astype(F32)
    th_c = jnp.einsum("bh,bc->hc", hs[:, :, 0], onehot)
    nv_c = jnp.einsum("b,bc->c", nv[:, 0], onehot)
    gate_c = (th_c / jnp.maximum(nv_c, 1.0)) > hdp["tau_h"]     # [H, C]
    gate_b = gate_c[:, chunk_of_block]                            # [H, nb]
    o = o * gate_b.T[:, :, None, None]
    o_pf = o.transpose(1, 0, 2, 3).reshape(H, S_pf, hd)

    # ---- generated rows: one block row each, keys = cache pages
    q_d, k_d, v_d = qkv(x_dec, pos_dec)
    S_d = S_pf + x_dec.shape[0]
    n_dec = (pos_dec >= 0).sum()

    def cache_of(pf, dec):
        full = jnp.zeros((pf.shape[0], S_d, hd), F32)
        full = jax.lax.dynamic_update_slice_in_dim(full, pf, 0, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(full, dec, plen - 1, axis=1)

    kc, vc = cache_of(k_pf, k_d), cache_of(v_pf, v_d)
    kpos = jnp.arange(S_d)
    kpos = jnp.where(kpos < plen - 1 + n_dec, kpos, -1)
    row = {**hdp, "pool": False}

    def dec_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q_d, i * QB, QB, axis=1)
        pb = jax.lax.dynamic_slice_in_dim(pos_dec, i * QB, QB)
        o, hs, nv = _attend_block(qb, kc, vc, pb, kpos, row, window, bk)
        gate = (hs / jnp.maximum(nv[None], 1.0)) > hdp["tau_h"]   # [H, QB]
        return o * gate[:, :, None]

    od = jax.lax.map(dec_block, jnp.arange(x_dec.shape[0] // QB))
    o_d = od.transpose(1, 0, 2, 3).reshape(H, -1, hd)
    return finish(x_pf, o_pf), finish(x_dec, o_d)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, w_norm, lm, served, extra, *, eps, precision):
    """Logits of a block of generated rows -> (max, argmax, logit of the
    served token, logit of the ``extra`` token) per row."""
    h = _rms(x, w_norm, eps)
    logits = _mm(h, lm, precision)
    at = lambda t: jnp.take_along_axis(logits, t[:, None], axis=1)[:, 0]
    return logits.max(-1), jnp.argmax(logits, -1), at(served), at(extra)


def _ceil(x, m):
    return -(-x // m) * m


def served_logits(config: dict, params, prompt: Sequence[int],
                  served: Sequence[int], *, precision: str = "fp32",
                  extra: Optional[Sequence[int]] = None) -> Dict[str, np.ndarray]:
    """Reference logits at each served position of one request.

    ``served`` are the tokens the engine generated for ``prompt``; row i
    is the step that produced ``served[i]``. Returns per row: ``max``
    (the reference's best logit), ``argmax``, ``served`` (the logit of
    the served token) and ``extra`` (the logit of ``extra[i]``, e.g. the
    token the control put first), all float64/int64 numpy arrays."""
    D = dims(config)
    dep = config["deployment"]
    h = config["hdp"]
    hdp = {"rho_b": float(h["rho_b"]), "tau_h": float(h["tau_h"]),
           "int_bits": int(h["int_bits"]), "frac_bits": int(h["frac_bits"]),
           "approx": bool(h["approx"]), "block_k": int(h["block_k"]),
           "grid_step": float(dep["kv_pool"]["grid_step"])}
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    plen, n = len(prompt), len(served)
    lp_len, chunks = prefill_plan(plen, dep["prefill_buckets"], dep["max_len"])
    big = max(dep["prefill_buckets"])
    S_pf = _ceil(lp_len, big)                  # few shapes: few compiles
    n_b = _ceil(n, 256)
    tok_pf = np.full(S_pf, prompt[-1], np.int32)
    tok_pf[:plen] = prompt
    pos_pf = np.where(np.arange(S_pf) < lp_len, np.arange(S_pf), -1)
    tok_d = np.zeros(n_b, np.int32)
    tok_d[0] = prompt[-1]
    tok_d[1:n] = served[:-1]
    pos_d = np.where(np.arange(n_b) < n, plen - 1 + np.arange(n_b), -1)
    chunk_of_block = np.zeros(S_pf // QB, np.int32)
    for c, (off, clen) in enumerate(chunks):
        chunk_of_block[off // QB:(off + clen) // QB] = c
    chunk_of_block[lp_len // QB:] = len(chunks)   # no rows: own group
    static = (tuple(sorted(D.items())), tuple(sorted(hdp.items())), precision,
              len(chunks) + 1, config["torch_dtype"])

    # each piece moves to the one device before use (a no-op for weights
    # already on it)
    dev = min(params["embed"]["tok"].devices(), key=lambda d: d.id)
    put = functools.partial(jax.device_put, device=dev)
    emb = put(params["embed"]["tok"])
    with jax.default_matmul_precision("highest"):
        x_pf = emb[put(tok_pf)].astype(F32)
        x_d = emb[put(tok_d)].astype(F32)
        args = (put(pos_pf), put(pos_d), put(chunk_of_block),
                put(np.int32(plen)))
        for li in range(D["L"]):
            lp = jax.tree.map(lambda w: put(w[li]), params["layers"])
            x_pf, x_d = _layer(lp, x_pf, x_d, *args, static=static)
        del x_pf, lp
        lm = params["embed"].get("lm_head")
        lm = emb.T if lm is None else put(lm)
        w_norm = put(params["final_norm"]["w"])
        sv = np.zeros(n_b, np.int32)
        sv[:n] = served
        ex = np.zeros(n_b, np.int32)
        if extra is not None:
            ex[:n] = np.asarray(extra, np.int32)
        outs = []
        for i in range(0, n_b, QB):
            outs.append(jax.device_get(_head(
                x_d[i:i + QB], w_norm, lm, put(sv[i:i + QB]),
                put(ex[i:i + QB]), eps=D["eps"], precision=precision)))
    mx, am, at_s, at_e = (np.concatenate([o[j] for o in outs])[:n]
                          for j in range(4))
    return {"max": mx.astype(np.float64), "argmax": am.astype(np.int64),
            "served": at_s.astype(np.float64), "extra": at_e.astype(np.float64)}
