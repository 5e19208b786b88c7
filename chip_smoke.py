"""Smoke run of the serving path on a TPU: the quickest proof that the
system still starts on the chip. Not a benchmark: every time and rate it
prints is a smoke number.

    python3 chip_smoke.py              # one chip: qwen2-1.5b, full width
    python3 chip_smoke.py --chips 4    # the four-chip paths only

One chip: builds ``repro.serving.Engine`` for qwen2-1.5b at its
published widths (28 layers, d_model 1536, vocab 151,936, bf16, seeded
random weights) with the serving defaults — paged layout, int8 KV pool,
HDP on, automatic backends, so decode runs the Pallas paged kernel — and
serves 8 requests with prompts of 4-12 pages for 16 new tokens. It
checks that every request completes (the engine's per-step tripwire
fails any request whose logits go non-finite), that decode resolved to
``pallas_paged_decode``, and that the Pallas decode attention agrees with
the XLA paged stage on the same pool state.

``--chips 4`` runs only the paths that exist across chips, each beside
what it is compared with: tensor-parallel serving of h2o-danube-1.8b at
tp=4 (a page pool larger than one chip's memory, sharded over the kv
heads) against tp=1, and four data-parallel qwen2-1.5b replicas, one per
chip, against a single engine.

Every phase runs in this one process, which holds the chip(s). The last
line of standard output is one JSON object, ``{"ok": true, "device":
...}``, printed only when every phase passed on a TPU; anything else
exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

#: Pallas decode vs the XLA paged stage (run at full f32 precision) on
#: the same pool: relative L2 error of the attention outputs.
DECODE_REL_TOL = 1e-2

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile = {"s": 0.0, "n": 0, "hits": 0}


def _log(*parts) -> None:
    print(*parts, flush=True)


def _watch_compiles() -> None:
    """Count compile seconds (cache reads included) and cache hits."""
    import jax

    def on_duration(event, duration, **_):
        if event == _COMPILE_EVENT:
            _compile["s"] += duration
            _compile["n"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _compile["hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def _compiled_since(mark: dict) -> dict:
    return {k: _compile[k] - mark[k] for k in _compile}


@dataclasses.dataclass(frozen=True)
class Plan:
    """Traffic of one phase: prompts of ``pages`` (lo, hi) whole pages of
    seeded random tokens, ``max_new`` greedy tokens each."""

    requests: int = 8
    pages: tuple = (4, 12)
    max_new: int = 16
    max_len: int = 2048


def prompts_for(cfg, plan: Plan, seed: int):
    from repro.serving.kv_cache import default_page_size

    ps = default_page_size(cfg)
    rng = np.random.default_rng(seed)
    lens = rng.integers(plan.pages[0] * ps, plan.pages[1] * ps + 1,
                        size=plan.requests)
    return [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
            for n in lens]


def serve(eng, prompts, max_new: int, uid0: int = 0):
    """Serve ``prompts`` to completion; returns uid -> tokens, checking
    every request finished ok with all its tokens."""
    from repro.serving import Request

    for i, p in enumerate(prompts):
        eng.submit(Request(uid0 + i, p, max_new_tokens=max_new))
    res = eng.run()
    out = {}
    for i in range(len(prompts)):
        r = res[uid0 + i]
        if r.status != "ok" or len(r.tokens) != max_new:
            raise AssertionError(
                f"request {uid0 + i}: status {r.status} ({r.error}), "
                f"{len(r.tokens)}/{max_new} tokens")
        out[i] = list(r.tokens)
    return out


def _agreement(a: dict, b: dict) -> float:
    same = sum(x == y for k in a for x, y in zip(a[k], b[k]))
    return same / max(1, sum(len(v) for v in a.values()))


def decode_check(eng, prompts, plan: Plan, *, seed: int):
    """Pallas paged decode vs the XLA paged stage on one pool state.

    Admits ``prompts`` and runs one decode step, then attends the pool
    of each checked layer with the same seeded queries through both
    backends (XLA at full f32 precision). Returns the worst relative L2
    error; raises when it exceeds :data:`DECODE_REL_TOL`.
    """
    import jax
    import jax.numpy as jnp

    from repro.attention.registry import get_backend
    from repro.models.attention import build_attn_call
    from repro.serving import Request

    cfg = eng.cfg
    for i, p in enumerate(prompts):
        eng.submit(Request(10_000 + i, p, max_new_tokens=plan.max_new))
    eng.step()                  # admit (prefill) + one decode step
    cache, table = eng.pages.cache, eng.pages.table()
    B, nP = table.shape
    ps = eng.pages.page_size
    # every written position precedes the next decode position
    pos = (eng._pos - 1)[:, None]
    ar = jnp.arange(nP * ps)
    k_pos = jnp.where(ar[None, :] <= pos, ar, -1)[:, None, None, :]
    q_pos = pos[:, None, None, :]
    call = build_attn_call(cfg, mode="decode", paged=True, per_slot=True)
    N, G, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    q = 2.0 * jax.random.normal(jax.random.PRNGKey(seed), (B, N, G, 1, hd))

    @functools.partial(jax.jit, static_argnums=0)
    def attend(name, cache_l, q, table, q_pos, k_pos):
        return get_backend(name).run(q, None, None, call, q_pos=q_pos,
                                     k_pos=k_pos, cache=cache_l,
                                     page_table=table)[0]

    worst = 0.0
    for layer in sorted({0, cfg.n_layers // 2, cfg.n_layers - 1}):
        args = ({k: v[layer] for k, v in cache.items()}, q, table, q_pos,
                k_pos)
        got = attend("pallas_paged_decode", *args)
        with jax.default_matmul_precision("highest"):
            want = attend("paged_hdp_decode", *args)
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        if not (np.isfinite(got).all() and np.isfinite(want).all()):
            raise AssertionError(f"layer {layer}: non-finite attention")
        err = float(np.linalg.norm(got - want)
                    / max(np.linalg.norm(want), 1e-30))
        _log(f"decode check layer {layer}: rel_l2 {err:.3e} "
             f"max_abs {np.abs(got - want).max():.3e}")
        worst = max(worst, err)
    eng.run()                   # drain the admitted requests
    if worst > DECODE_REL_TOL:
        raise AssertionError(
            f"Pallas decode disagrees with the XLA stage: rel_l2 {worst:.3e}"
            f" > {DECODE_REL_TOL}")
    return worst


def one_chip(cfg, plan: Plan, *, seed: int = 0,
             expect_decode: str = "pallas_paged_decode") -> dict:
    """The one-chip smoke: serve, recompile from the cache, serve warm,
    check Pallas decode against XLA, and compare tokens with an engine
    whose decode runs the XLA paged backend."""
    import jax

    from repro.attention import AttnSpec
    from repro.launch.serve import prefill_buckets
    from repro.models import registry
    from repro.serving import Engine
    from repro.serving.kv_cache import default_page_size

    ps = default_page_size(cfg)
    kw = dict(max_batch=plan.requests, max_len=plan.max_len,
              prefill_buckets=prefill_buckets(ps, plan.max_len),
              collect_stats=True)
    params, _ = registry.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = prompts_for(cfg, plan, seed)
    _log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
         f"vocab {cfg.vocab_size}, {cfg.dtype}; page {ps} tokens; prompts "
         f"{sorted(len(p) for p in prompts)} tokens; buckets {kw['prefill_buckets']}")

    mark = dict(_compile)
    eng = Engine(cfg, params=params, **kw)
    decode_backend = eng.resolved_backend("decode")
    _log(f"attn_prefill: {eng.resolved_backend('prefill')} attn_decode: "
         f"{decode_backend} kv_dtype: {eng.kv_dtype}")
    if decode_backend != expect_decode:
        raise AssertionError(f"decode resolved to {decode_backend}, "
                             f"expected {expect_decode}")
    toks = serve(eng, prompts, plan.max_new)
    cold = _compiled_since(mark)
    del eng

    # the same programs again, from a fresh engine: every compile is now
    # a persistent-cache read
    mark = dict(_compile)
    eng = Engine(cfg, params=params, **kw)
    again = serve(eng, prompts, plan.max_new, uid0=100)
    cached = _compiled_since(mark)
    if again != toks:
        raise AssertionError("a rebuilt engine changed the tokens")
    _log(f"compile_s cold {cold['s']:.2f} ({cold['n']} programs, "
         f"{cold['hits']} cache hits); cached {cached['s']:.2f} "
         f"({cached['n']} programs, {cached['hits']} cache hits)")

    eng.reset_metrics()
    t0 = time.perf_counter()
    serve(eng, prompts, plan.max_new, uid0=200)
    wall = time.perf_counter() - t0
    s = eng.summary()
    _log(f"smoke (not a benchmark): decode_tok_s {s['decode_tok_s']:.2f} "
         f"over {s['tokens_out']} tokens, warm pass wall {wall:.3f} s")
    _log(f"sparsity: block {s['block_sparsity']:.4f} head "
         f"{s['head_sparsity']:.4f} page {s['page_sparsity']:.4f}")

    err = decode_check(eng, prompts, plan, seed=seed)
    del eng

    xla = Engine(cfg, params=params, attn=AttnSpec(decode="paged_hdp_decode"),
                 **kw)
    xla_toks = serve(xla, prompts, plan.max_new, uid0=300)
    agree = _agreement(toks, xla_toks)
    _log(f"tokens, Pallas vs XLA decode engine: {agree:.4f} agree "
         "(reported, not required: XLA's f32 dots default to reduced "
         "precision on TPU)")
    return {"decode_backend": decode_backend, "compile_s_cold": cold["s"],
            "compile_s_cached": cached["s"], "decode_rel_l2": err,
            "token_agreement": agree}


def tp_vs_single(cfg, plan: Plan, *, seed: int = 0, tp: int = 4,
                 pool_bytes_min: int = 0) -> dict:
    """Tensor-parallel serving at ``tp`` on a pool of at least
    ``pool_bytes_min`` bytes against tp=1 on a pool that fits one chip:
    same params, same requests, the same tokens."""
    import jax

    from repro.models import registry
    from repro.serving import Engine

    kw = dict(max_batch=plan.requests, max_len=plan.max_len,
              prefill_buckets=(plan.max_len,))
    params, _ = registry.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = prompts_for(cfg, plan, seed)

    one = Engine(cfg, params=params, **kw)
    ref = serve(one, prompts, plan.max_new)
    one_bytes = one.pages.pool_bytes()
    # the pool's own per-page accounting sizes the sharded pool
    pages = max(-(-pool_bytes_min // one.pages.page_bytes()),
                one.pages.num_pages)
    del one

    eng = Engine(cfg, params=params, tp=tp, num_pages=pages, **kw)
    del params
    got = serve(eng, prompts, plan.max_new)
    total, shard = eng.pages.pool_bytes(), eng.pages.pool_bytes_per_shard()
    leaf = eng.pages.cache["k_pages"]
    resident = {s.device.id: s.data.nbytes for s in leaf.addressable_shards}
    _log(f"tp={tp} {cfg.name}: attn_decode {eng.resolved_backend('decode')}; "
         f"pool {total} B total, {shard} B per shard (tp=1 pool "
         f"{one_bytes} B); k_pages resident per device {resident}")
    if got != ref:
        raise AssertionError(f"tp={tp} tokens differ from tp=1")
    if shard * tp != total or any(
            n * tp != leaf.nbytes for n in resident.values()):
        raise AssertionError(f"tp={tp}: pool not evenly sharded")
    if total < pool_bytes_min:
        raise AssertionError(f"pool {total} B < {pool_bytes_min} B")
    _log(f"tp={tp} vs tp=1: tokens identical over {len(ref)} requests")
    return {"tp": tp, "pool_bytes": total, "pool_bytes_per_shard": shard}


def dp_vs_single(cfg, plan: Plan, *, seed: int = 0, dp: int = 4) -> dict:
    """``dp`` replicas, replica i on device i, against one engine."""
    import jax

    from repro.models import registry
    from repro.serving import Engine, ReplicaSet, Request

    kw = dict(max_batch=plan.requests, max_len=plan.max_len,
              prefill_buckets=(plan.max_len,))
    params, _ = registry.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = prompts_for(cfg, plan, seed)
    one = Engine(cfg, params=params, **kw)
    ref = serve(one, prompts, plan.max_new)
    del one

    rs = ReplicaSet.build(cfg, dp, params=params, tp=1, **kw)
    got = {r.uid: list(r.tokens) for r in rs.serve(
        [Request(i, p, max_new_tokens=plan.max_new)
         for i, p in enumerate(prompts)])}
    devs = jax.devices()
    placed = [sorted(d.id for d in jax.tree.leaves(e.params)[0].devices())
              for e in rs.engines]
    s = rs.summary()
    _log(f"dp={dp} {cfg.name}: params on devices {placed}, pools on "
         f"{s['replica_devices']}, requests per replica "
         f"{s['requests_per_replica']}")
    want = [[d.id] for d in devs[:dp]]
    if placed != want or s["replica_devices"] != want:
        raise AssertionError(f"replicas not one per device: {placed}")
    if got != ref:
        raise AssertionError(f"dp={dp} tokens differ from the single engine")
    _log(f"dp={dp} vs one engine: tokens identical over {len(ref)} requests")
    return {"dp": dp, "placed": placed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip serving smoke; 4: only the "
                         "tensor-parallel and replica paths")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.configs import get_config
    from repro.launch.runtime import check_platform, enable_compile_cache

    dev = check_platform()
    if dev.platform != "tpu":
        _log(f"chip_smoke: needs a TPU, JAX runs on {dev.platform}")
        return 2
    n = len(jax.devices())
    if n < args.chips:
        _log(f"chip_smoke: --chips {args.chips} but {n} device(s)")
        return 2
    cache_dir = enable_compile_cache()
    _watch_compiles()
    _log(f"device_kind: {dev.device_kind} ({n} device(s)); compile cache "
         f"{cache_dir}")

    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(get_config("qwen2-1.5b"), Plan(), seed=args.seed)
    else:
        hbm = dev.memory_stats()["bytes_limit"]
        tp_vs_single(get_config("h2o-danube-1.8b"),
                     Plan(requests=4), seed=args.seed, tp=4,
                     pool_bytes_min=int(1.05 * hbm))
        dp_vs_single(get_config("qwen2-1.5b"), Plan(), seed=args.seed,
                     dp=4)
    stats = dev.memory_stats() or {}
    _log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} of "
         f"{stats.get('bytes_limit')} on device {dev.id}; total "
         f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
