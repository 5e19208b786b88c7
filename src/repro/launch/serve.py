"""Serving launcher: batched requests through the HDP engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --reduced --requests 16 --max-new 8

``--full`` serves the published widths (on a TPU; ``--max-len`` sets the
context, and the prefill buckets and prompt lengths follow the page size
and ``--max-len``). With ``JAX_PLATFORMS=cpu`` it runs on the CPU, Pallas
kernels in interpret mode; without it a TPU is required when the TPU
runtime is installed.

Drives `serving.Engine` (continuous batching, per-slot positions, HDP
prefill/decode) with synthetic prompts and reports throughput + achieved
HDP sparsity. `--no-hdp` serves the identical model with dense attention
for an A/B of output agreement and step cost. `--stream-sched` (with an
optional seeded `--arrival-rate` Poisson request stream) serves through
the continuous-batching scheduler and additionally reports TTFT / TPOT /
queue-depth stats.
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import os
import sys

import numpy as np

from repro.attention import AttnSpec
from repro.configs import get_config
from repro.configs.base import reduced
from repro.launch.runtime import check_platform, enable_compile_cache
from repro.serving import Engine, ReplicaSet, Request, SchedulerConfig
from repro.serving.engine import MESH_DP_ENV
from repro.serving.kv_cache import default_page_size

log = logging.getLogger("repro.serve")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--no-hdp", action="store_true")
    ap.add_argument("--rho-b", type=float, default=None)
    ap.add_argument("--tau-h", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="auto",
                    help="attention backend name or family tag from the "
                         "repro.attention registry (auto | reference | xla | "
                         "pallas | an exact name like paged_hdp_decode)")
    ap.add_argument("--layout", default="auto",
                    choices=["auto", "paged", "dense"],
                    help="serving cache layout: paged = block-paged KV cache "
                         "(FUM page gather); dense = per-slot contiguous")
    ap.add_argument("--kv-dtype", default="auto",
                    choices=["auto", "fp32", "int8", "fp8_v"],
                    help="paged KV pool storage format: int8 = per-page "
                         "scaled int8 codes (the default store), fp8_v = "
                         "int8 K + fp8 V, fp32 = the full-precision A/B "
                         "oracle. auto honors REPRO_KV_DTYPE, else int8; "
                         "dense layout always serves fp32")
    ap.add_argument("--tp", type=int, default=None,
                    help="tensor-parallel degree: shard the paged KV pool "
                         "and the decode attention over a jax mesh's "
                         "'model' (head) axis; token-identical to tp=1. "
                         "Needs >= tp devices (on CPU: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N). "
                         "Default honors REPRO_MESH_TP, else 1")
    ap.add_argument("--dp", type=int, default=None,
                    help="data-parallel engine replicas behind one "
                         "dispatching front-end (prefix-affinity then "
                         "least-loaded); replicas share one params tree so "
                         "tokens are dispatch-invariant. Default honors "
                         "REPRO_MESH_DP, else 1")
    ap.add_argument("--kv-scale", default="grid",
                    choices=["grid", "absmax"],
                    help="int8 KV pool scale calibration: grid = one static "
                         "power-of-two scale (bit-parity with the scout "
                         "grid); absmax = per-page per-kv-head calibrated "
                         "scales (lower round-trip error, drift-gated "
                         "rather than bit-exact vs the scout)")
    ap.add_argument("--calib", default=None,
                    help="override hdp calibration (the paged scout stores "
                         "a write-time int8 copy, i.e. calib-free)")
    ap.add_argument("--decode-horizon", type=int, default=None,
                    help="tokens per fused decode call (jitted lax.scan "
                         "loop): one host sync per horizon instead of per "
                         "token, token-identical to 1; default honors "
                         "REPRO_DECODE_HORIZON, else 1")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=None,
                    help="share prompt-prefix pages across requests through "
                         "the refcounted radix tree (paged layout); prefill "
                         "runs only on the unshared suffix. Default honors "
                         "REPRO_PREFIX_CACHE, else off")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="force prefix caching off (the cold A/B leg)")
    ap.add_argument("--spec-decode", dest="spec_decode",
                    action="store_true", default=None,
                    help="self-speculative decode: per round, draft-len-1 "
                         "approximate draft steps (int8-scout attention) "
                         "plus ONE multi-query verify over the serving "
                         "cache; token-identical to plain greedy decode. "
                         "Default honors REPRO_SPEC_DECODE, else off")
    ap.add_argument("--no-spec-decode", dest="spec_decode",
                    action="store_false",
                    help="force speculative decode off (the A/B baseline)")
    ap.add_argument("--draft-len", type=int, default=None,
                    help="tokens proposed+verified per speculative round; "
                         "default honors REPRO_DRAFT_LEN, else 4")
    ap.add_argument("--adaptive-spec", dest="adaptive_spec",
                    action="store_true", default=None,
                    help="acceptance-adaptive speculation: an EMA of the "
                         "draft acceptance rate re-plans draft length and "
                         "draft prune aggressiveness per round "
                         "(token-identical at any plan). Default honors "
                         "REPRO_ADAPTIVE_SPEC, else off")
    ap.add_argument("--no-adaptive-spec", dest="adaptive_spec",
                    action="store_false",
                    help="force adaptive speculation off (fixed draft_len)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give every synthetic prompt a common random "
                         "prefix of this many tokens (the prefix-cache "
                         "benchmark workload); 0 = fully random prompts")
    ap.add_argument("--stream-sched", dest="stream_sched",
                    action="store_true", default=None,
                    help="continuous-batching stream scheduler: token-"
                         "budget admission, prefix-hit-first ordering, "
                         "mid-run slot recycling, chunked prefill "
                         "interleaved with decode. Token-identical to "
                         "static serving. Default honors "
                         "REPRO_STREAM_SCHED, else off")
    ap.add_argument("--no-stream-sched", dest="stream_sched",
                    action="store_false",
                    help="force the stream scheduler off (the static A/B "
                         "leg)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="mean request arrivals per engine step (Poisson "
                         "process, seeded): requests are submitted while "
                         "the engine is already decoding, exercising mid-"
                         "run admission. 0 = submit everything up front. "
                         "Needs --stream-sched")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="interleaved-prefill token budget per engine step "
                         "for long prompts under the stream scheduler; "
                         "default = one largest-bucket chunk per step")
    ap.add_argument("--watchdog-steps", type=int, default=500,
                    help="no-progress engine steps with requests pending "
                         "before the stream scheduler's watchdog sheds the "
                         "stalled queue head (raises past its escalation "
                         "threshold)")
    ap.add_argument("--fault-plan", default=None,
                    help="deterministic fault-injection schedule "
                         "('kind@step[:k=v,..];...', kinds exhaust | error "
                         "| nan | slow | kill — see repro.serving.faults). "
                         "Steps count engine steps; --warmup pauses "
                         "injection and restarts step numbering afterwards, "
                         "so fault steps always index the measured traffic. "
                         "Default honors REPRO_FAULT_PLAN, else no faults")
    ap.add_argument("--warmup", action="store_true",
                    help="run one throwaway request through the engine and "
                         "reset metrics before serving, so reported tok/s "
                         "is steady-state rather than jit-compile time "
                         "(what the benchmark A/B records)")
    return ap


def prefill_buckets(page_size: int, max_len: int) -> tuple:
    """Prefill pad-to lengths: the power-of-two multiples of the page size
    from 16 tokens up to ``max_len`` (just ``max_len`` when no page fits).
    Page-aligned buckets keep HDP's blocks whole, and a prompt longer than
    the largest bucket prefills in chunks of it."""
    b = page_size
    while b < 16:
        b *= 2
    out = []
    while b <= max_len:
        out.append(b)
        b *= 2
    return tuple(out) or (max_len,)


def prompt_cap(page_size: int, max_len: int) -> int:
    """Longest synthetic prompt: half the context, in whole pages."""
    return max(page_size, max_len // 2 // page_size * page_size)


def run(args) -> dict:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.hdp is not None:
        hdp = cfg.hdp
        if args.no_hdp:
            hdp = dataclasses.replace(hdp, enabled=False)
        if args.rho_b is not None:
            hdp = dataclasses.replace(hdp, rho_b=args.rho_b)
        if args.tau_h is not None:
            hdp = dataclasses.replace(hdp, tau_h=args.tau_h)
        if args.calib is not None:
            hdp = dataclasses.replace(hdp, calib=args.calib)
        cfg = cfg.replace(hdp=hdp)

    spec = AttnSpec(backend=args.backend, layout=args.layout,
                    kv_dtype=getattr(args, "kv_dtype", "auto"),
                    kv_scale=getattr(args, "kv_scale", "grid"))
    stream = getattr(args, "stream_sched", None)
    sched_cfg = SchedulerConfig(
        prefill_chunk_tokens=getattr(args, "prefill_chunk", None),
        watchdog_steps=getattr(args, "watchdog_steps", 500)) \
        if stream else None
    dp = getattr(args, "dp", None)
    if dp is None:
        dp = int(os.environ.get(MESH_DP_ENV) or 1)
    dp = max(int(dp), 1)
    ps = default_page_size(cfg)
    engine_kw = dict(max_batch=args.max_batch, max_len=args.max_len,
                     prefill_buckets=prefill_buckets(ps, args.max_len),
                     collect_stats=not args.no_hdp, attn=spec,
                     prefix_cache=args.prefix_cache,
                     decode_horizon=args.decode_horizon,
                     spec_decode=args.spec_decode,
                     draft_len=args.draft_len,
                     adaptive_spec=getattr(args, "adaptive_spec", None),
                     stream_sched=stream, sched=sched_cfg,
                     tp=getattr(args, "tp", None))
    fault_plan = getattr(args, "fault_plan", None)
    if dp > 1:
        eng = ReplicaSet.build(cfg, dp, faults=fault_plan, **engine_kw)
        engines = eng.engines
    else:
        eng = Engine(cfg, faults=fault_plan, **engine_kw)
        engines = [eng]
    eng0 = engines[0]
    if getattr(args, "warmup", False):
        # one throwaway request PER REPLICA compiles the prefill/decode
        # jits (same max_new as the real batch, so every fused-loop scan
        # length the drain will need is warm), then the counters restart
        # from zero. Fault injection is paused and step numbering restarts
        # afterwards, so scheduled fault steps index the measured traffic.
        paused = [e.faults for e in engines]
        for e in engines:
            e.faults = None
        for e in engines:
            e.submit(Request(-1, [1, 2, 3, 4], max_new_tokens=args.max_new))
            e.run()
            e._results.pop(-1, None)
        for e, f in zip(engines, paused):
            e.faults = f
            e._cur_step = 0
        eng.reset_metrics()
    if args.shared_prefix \
            and args.max_len - args.max_new - args.shared_prefix < 5:
        raise SystemExit(
            f"--shared-prefix {args.shared_prefix} leaves no room for "
            f"prompt tails: need max_len >= shared_prefix + max_new + 5 "
            f"(max_len {args.max_len}, max_new {args.max_new})")
    rng = np.random.default_rng(args.seed)
    shared = rng.integers(1, cfg.vocab_size,
                          size=args.shared_prefix).tolist()
    prompts = []
    for uid in range(args.requests):
        hi = min(prompt_cap(ps, args.max_len),
                 args.max_len - args.max_new - args.shared_prefix)
        plen = int(rng.integers(4, max(hi, 5)))
        prompts.append(shared
                       + rng.integers(1, cfg.vocab_size, size=plen).tolist())

    arrival_rate = getattr(args, "arrival_rate", 0.0) or 0.0
    if arrival_rate > 0 and eng0.sched is None:
        raise SystemExit("--arrival-rate needs --stream-sched")
    if arrival_rate > 0:
        # Poisson arrivals in engine-step time, drawn AFTER the prompts
        # so the prompt stream (and tokens_fp) matches the static and
        # solo A/B legs token for token
        gaps = rng.exponential(1.0 / arrival_rate, size=args.requests)
        arrive = np.floor(np.cumsum(gaps)).astype(int)
        pending = list(range(args.requests))
        step = 0
        while pending or eng._n_pending():
            while pending and arrive[pending[0]] <= step:
                uid = pending.pop(0)
                eng.submit(Request(uid, prompts[uid],
                                   max_new_tokens=args.max_new))
            eng.step()
            step += 1
            if step > 100_000:
                raise SystemExit("serve: arrival loop exceeded 100k steps")
        results = eng.results()
    else:
        for uid, prompt in enumerate(prompts):
            eng.submit(Request(uid, prompt, max_new_tokens=args.max_new))
        results = eng.run()
    if dp > 1:
        # replica-0 summary carries the shape/backends; throughput and
        # counter fields are re-aggregated over the fleet
        fleet = eng.summary()
        subs = fleet["replicas"]
        s = dict(subs[0])
        for k in ("tokens_out", "decode_s", "prefill_s", "prefill_calls",
                  "prefill_tokens", "decode_steps", "cache_bytes",
                  "req_cancelled", "req_deadline", "req_errors",
                  "sched_preempted", "watchdog_shed", "faults_injected",
                  "queue_rejected"):
            s[k] = sum(sub.get(k, 0) for sub in subs)
        if s.get("decode_s"):
            s["decode_tok_s"] = s["tokens_out"] / s["decode_s"]
        for k in ("block_sparsity", "head_sparsity", "page_sparsity"):
            vals = [sub.get(k, 0.0) for sub in subs]
            s[k] = sum(vals) / len(vals)
        for k in ("health", "failovers", "requests_failed_over",
                  "replica_queue_depth", "replica_inflight",
                  "replica_last_step_s", "fault_plan", "faults_fired"):
            if k in fleet:
                s[k] = fleet[k]
        s["requests_per_replica"] = fleet["requests_per_replica"]
    else:
        s = eng.summary()
    done = sum(len(r.tokens) == args.max_new for r in results.values())
    # order-independent fingerprint of every generated token — the A/B's
    # byte-identity check (prefix-cache hit vs cold must agree exactly)
    tokens_fp = hash(tuple(sorted(
        (u, tuple(r.tokens)) for u, r in results.items()))) & 0xffffffff
    out = {
        "requests": args.requests,
        "completed": done,
        "backend": s["cache_backend"],
        # resolved (post-fallback) attention backends, one per phase — the
        # attributable ground truth for benchmark A/B rows
        "attn_prefill": s["attn_backend_prefill"],
        "attn_decode": s["attn_backend_decode"],
        "decode_horizon": eng0.horizon,
        # tokens over decode_s: host time from each decode dispatch to its
        # results, which also waits out prefills queued on the device
        "decode_tok_s": round(s.get("decode_tok_s", 0.0), 2),
        # host time dispatching prefill programs (not their device time)
        "prefill_s_total": round(s["prefill_s"], 3),
        "prefill_calls": s["prefill_calls"],
        # tokens run through prefill forwards (padded size) — the
        # deterministic FLOPs proxy; prefix-cache hits shrink it
        "prefill_tokens": int(s["prefill_tokens"]),
        "decode_steps": s["decode_steps"],
        "block_sparsity": round(s["block_sparsity"], 4),
        "head_sparsity": round(s["head_sparsity"], 4),
        "page_sparsity": round(s["page_sparsity"], 4),
        "kv_dtype": s["kv_dtype"],
        "kv_scale": s.get("kv_scale", "grid"),
        "cache_bytes": s["cache_bytes"],
        "tokens_fp": tokens_fp,
        "spec_decode": s["spec_decode"],
        "stream_sched": s["stream_sched"],
        "tp": int(s.get("tp", 1)),
        "dp": dp,
    }
    # request-lifecycle accounting: every submitted request must come back
    # as SOME typed Result even under injected faults — "lost" (no Result
    # at all) is the failure mode the fault harness exists to catch
    out["requests_ok"] = sum(r.status == "ok" for r in results.values())
    out["requests_failed"] = sum(
        r.status != "ok" for r in results.values())
    out["requests_lost"] = args.requests - len(results)
    if "fault_plan" in s:
        out["fault_plan"] = s["fault_plan"]
        out["faults_fired"] = int(s["faults_fired"])
        out["req_cancelled"] = int(s.get("req_cancelled", 0))
        out["req_deadline"] = int(s.get("req_deadline", 0))
        out["req_errors"] = int(s.get("req_errors", 0))
        out["sched_preempted"] = int(s.get("sched_preempted", 0))
        out["watchdog_shed"] = int(s.get("watchdog_shed", 0))
    if "mesh_shape" in s:
        out["mesh"] = s["mesh_shape"]
        out["cache_bytes_pool_per_shard"] = s["cache_bytes_pool_per_shard"]
        out["collective_bytes_per_layer"] = s["collective_bytes_per_layer"]
    if dp > 1:
        out["requests_per_replica"] = s["requests_per_replica"]
        out["replica_health"] = s.get("health", [])
        out["failovers"] = int(s.get("failovers", 0))
        out["requests_failed_over"] = int(s.get("requests_failed_over", 0))
        out["replica_queue_depth"] = s.get("replica_queue_depth", [])
        out["replica_inflight"] = s.get("replica_inflight", [])
        out["replica_last_step_s"] = [
            round(float(v), 5) for v in s.get("replica_last_step_s", [])]
    if "meas_decode_step_s" in s:
        out["meas_decode_step_s"] = round(s["meas_decode_step_s"], 6)
    if s["stream_sched"]:
        out.update(
            sched_admitted=int(s["sched_admitted"]),
            sched_recycled=int(s["sched_recycled"]),
            sched_deferred=int(s["sched_deferred"]),
            sched_chunk_tokens=int(s["sched_chunk_tokens"]),
            sched_interleaved_steps=int(s["sched_interleaved_steps"]),
            queue_depth_peak=int(s["queue_depth_peak"]),
            queue_depth_mean=round(s.get("queue_depth_mean", 0.0), 3),
            ttft_s_mean=round(s.get("ttft_s_mean", 0.0), 4),
            ttft_s_p95=round(s.get("ttft_s_p95", 0.0), 4),
            tpot_s_mean=round(s.get("tpot_s_mean", 0.0), 5),
            queue_wait_s_mean=round(s.get("queue_wait_s_mean", 0.0), 4))
    if s["spec_decode"]:
        out.update(draft_len=s["draft_len"],
                   spec_rounds=int(s["spec_rounds"]),
                   draft_tokens=int(s["draft_tokens"]),
                   accepted_tokens=int(s["accepted_tokens"]),
                   acceptance_rate=round(s["acceptance_rate"], 4),
                   attn_draft=s["attn_backend_draft"],
                   attn_verify=s["attn_backend_verify"],
                   adaptive_spec=s["adaptive_spec"])
        if s["adaptive_spec"]:
            out.update(
                acceptance_ema=round(s["acceptance_ema"], 4),
                draft_len_mean=round(s["draft_len_mean"] or 0.0, 3))
    if s["cache_backend"] == "paged":
        out["pages_peak"] = s["pages_peak"]
        out["pages_in_use"] = s["pages_in_use"]
        # resident-footprint accounting by storage dtype: pool bytes over
        # every leaf (codes + per-page scales) and the per-token rate
        out["cache_bytes_pool"] = s["cache_bytes_pool"]
        out["cache_bytes_per_token"] = round(s["cache_bytes_per_token"], 2)
        out["prefix_cache"] = s["prefix_cache"]
        if s["prefix_cache"]:
            out.update(prefix_hits=s["prefix_hits"],
                       prefix_hit_tokens=s["prefix_hit_tokens"],
                       prefix_evictions=s["prefix_evictions"],
                       pages_cached=s["pages_cached"],
                       cow_copies=int(s["cow_copies"]))
    log.info("serve summary: %s", out)
    return out


def main(argv=None) -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    args = build_parser().parse_args(argv)
    check_platform()
    enable_compile_cache()
    out = run(args)
    if out.get("fault_plan"):
        # under injected faults some requests fail BY DESIGN — success is
        # "no request lost": every submission came back as a typed Result
        return 0 if out["requests_lost"] == 0 else 1
    return 0 if out["completed"] == out["requests"] else 1


if __name__ == "__main__":
    sys.exit(main())
