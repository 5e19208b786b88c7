"""Benchmark harness — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
        [--json [PATH]] [--horizon H]

| name           | paper artifact                 |
|----------------|--------------------------------|
| block_pruning  | Fig. 7  (HDP vs Top-K)         |
| head_pruning   | Fig. 8 + Fig. 11 (SpAtten)     |
| approximation  | Fig. 9                         |
| net_pruning    | Fig. 10                        |
| kernels        | kernel correctness + FUM bytes |
| roofline       | dry-run roofline table (§g)    |
| serving        | end-to-end engine throughput   |
| serving_paged  | paged vs dense KV cache A/B    |
| serving_prefix | prefix-cache hit vs cold A/B   |
| serving_spec   | speculative decode vs H=4 A/B  |
| serving_stream | stream scheduler vs static/solo|
| serving_kvquant | int8/fp8_v KV pool vs fp32 oracle A/B |
| serving_tp     | tensor-parallel TP=1/2/4 sharded-pool A/B |
| serving_chaos  | goodput under injected faults vs clean A/B |

Accuracy is proxied by top-1 next-token agreement vs the dense model on
held-out synthetic data (no GLUE checkpoints offline — substitution
documented in DESIGN.md §1). All output is CSV-ish text; bench_output.txt
is the canonical artifact. ``--json`` additionally persists the serving
rows to BENCH_serving.json at the repo root (preserving the recorded
pre-existing ``baseline`` block) so the decode-path perf trajectory is
tracked across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

#: default artifact path for --json (repo root, next to this package)
BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_serving.json")


def bench_serving(quick: bool = False, backend: str = "auto",
                  horizon: int = 4):
    """End-to-end engine throughput, fused-decode-loop A/B included.

    Rows come in pairs per arch: decode_horizon=1 (per-token stepping,
    the pre-fusion hot path) and decode_horizon=``horizon`` — same
    engine, same tokens, one host sync per horizon."""
    from repro.launch import serve

    rows = []
    for arch in ("qwen2-1.5b", "granite-8b"):
        for no_hdp in (False, True):
            for h in dict.fromkeys((1, horizon)):
                # max-new 24 (vs the functional benches' 6) so decode
                # spans enough steps for a stable steady-state tok/s
                args = serve.build_parser().parse_args(
                    ["--arch", arch, "--requests", "4" if quick else "8",
                     "--max-new", "8" if quick else "24",
                     "--backend", backend, "--decode-horizon", str(h),
                     "--warmup"]
                    + (["--no-hdp"] if no_hdp else []))
                # every row records the RESOLVED backend per phase
                # (attn_prefill / attn_decode), so A/B rows stay
                # attributable when auto-selection or fallback changes
                out = serve.run(args)
                rows.append({"arch": arch, "hdp": not no_hdp, **out})
    print("# serving (reduced configs, continuous batching, horizon A/B)")
    hdr = list(rows[0].keys())
    print(",".join(str(h) for h in hdr))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in hdr))
    return rows


def bench_serving_paged(quick: bool = False, backend: str = "auto"):
    """Paged vs dense cache backend A/B: decode tok/s + resident cache bytes.

    With HDP enabled the paged backend stores the int8 scout copy but
    allocates pages per request (prompt + budget) instead of max_len per
    slot, and its decode gathers only scout-surviving pages — cache bytes
    must come out <= the dense per-slot layout, tokens identical (the
    write-time scout pins calib="none"; see DESIGN notes in
    serving/engine.py).
    """
    from repro.launch import serve

    rows = []
    for arch in ("qwen2-1.5b", "granite-8b"):
        for layout in ("paged", "dense"):
            args = serve.build_parser().parse_args(
                ["--arch", arch, "--requests", "4" if quick else "8",
                 "--max-new", "4" if quick else "6", "--backend", backend,
                 "--layout", layout, "--calib", "none", "--warmup"])
            out = serve.run(args)
            row = {"arch": arch, **out}
            row["backend"] = layout  # the A/B independent variable
            rows.append(row)
    print("# serving paged-vs-dense (reduced configs, HDP on, calib=none)")
    hdr = [h for h in rows[0] if h != "requests"]
    print(",".join(str(h) for h in hdr))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in hdr))
    by_arch = {}
    for r in rows:
        by_arch.setdefault(r["arch"], {})[r["backend"]] = r
    for arch, pair in by_arch.items():
        p, d = pair["paged"], pair["dense"]
        assert p["cache_bytes"] <= d["cache_bytes"], \
            f"{arch}: paged cache {p['cache_bytes']} > dense {d['cache_bytes']}"
        print(f"## {arch}: paged cache {p['cache_bytes']}B <= "
              f"dense {d['cache_bytes']}B "
              f"({1 - p['cache_bytes'] / max(d['cache_bytes'], 1):.0%} less), "
              f"page_sparsity {p['page_sparsity']}")
    return rows


def bench_serving_prefix(quick: bool = False, backend: str = "auto"):
    """Shared-prefix A/B: prefix-cache hits vs cold prefill.

    The workload is 8 requests whose prompts share a 256-token random
    prefix (distinct random tails), served twice through the same paged
    engine configuration: ``--prefix-cache`` (suffix-only prefill through
    the radix tree) and ``--no-prefix-cache`` (every prompt prefilled
    from scratch). Asserts the acceptance contract: byte-identical
    generated tokens (tokens_fp), prefill wall time reduced, and a page
    high-water mark that reflects sharing (shared prefix pages counted
    once instead of once per slot).
    """
    from repro.launch import serve

    rows = []
    for arch in ("qwen2-1.5b",) if quick else ("qwen2-1.5b", "granite-8b"):
        pair = {}
        for prefix_on in (True, False):
            args = serve.build_parser().parse_args(
                ["--arch", arch, "--requests", "8",
                 "--max-new", "4" if quick else "6",
                 "--max-len", "384", "--backend", backend, "--warmup",
                 "--shared-prefix", "256",
                 "--prefix-cache" if prefix_on else "--no-prefix-cache"])
            out = serve.run(args)
            row = {"arch": arch, "prefix": prefix_on, **out}
            row["backend"] = "prefix" if prefix_on else "cold"  # A/B variable
            rows.append(row)
            pair[prefix_on] = row
        hot, cold = pair[True], pair[False]
        assert hot["tokens_fp"] == cold["tokens_fp"], \
            f"{arch}: prefix-cache hits changed the generated tokens"
        assert hot["prefix_hits"] > 0, f"{arch}: workload produced no hits"
        assert hot["pages_peak"] < cold["pages_peak"], \
            f"{arch}: page peak {hot['pages_peak']} shows no sharing " \
            f"(cold {cold['pages_peak']})"
        # prefill-FLOPs proxy: tokens actually run through prefill
        # forwards (deterministic — wall time is reported, not asserted,
        # because it flakes on loaded CI runners)
        assert hot["prefill_tokens"] < cold["prefill_tokens"], \
            f"{arch}: prefill tokens {hot['prefill_tokens']} not below " \
            f"cold {cold['prefill_tokens']}"
        print(f"## {arch}: prefill {hot['prefill_tokens']} tokens vs cold "
              f"{cold['prefill_tokens']} "
              f"({1 - hot['prefill_tokens'] / max(cold['prefill_tokens'], 1):.0%} less), "
              f"wall {hot['prefill_s_total']}s vs {cold['prefill_s_total']}s, "
              f"pages_peak {hot['pages_peak']} vs {cold['pages_peak']}, "
              f"{hot['prefix_hits']} hits / {hot['prefix_hit_tokens']} "
              f"tokens, tokens byte-identical")
    print("# serving shared-prefix A/B (8 requests, 256-token shared prefix)")
    hdr = [h for h in rows[0] if h != "requests"]
    print(",".join(str(h) for h in hdr))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in hdr))
    return rows


def bench_serving_spec(quick: bool = False, backend: str = "auto"):
    """Self-speculative decode A/B: draft+verify rounds vs the fused H=4 loop.

    The workload is long-context decode (8 requests over a 256-token
    shared prefix, max_len 384) — the regime the draft's int8-scout
    bandwidth win targets: per round, ``draft_len - 1`` draft steps read
    only the two int8 scout copies plus surviving pages' V, and ONE
    multi-query verify reads the full-precision pool once for the whole
    round. Asserts the acceptance contract: byte-identical generated
    tokens (tokens_fp) vs the horizon-4 baseline at whatever acceptance
    rate the draft achieves, with at least one accepted draft token.
    Tok/s and the achieved acceptance rate are recorded per row (wall
    time is reported, not asserted — it flakes on loaded CI runners;
    median-of-3 steady-state runs on this workload measure ~1.2-1.4x
    over H=4 at draft_len 12, acceptance 1.0).
    """
    from repro.launch import serve

    rows = []
    for arch in ("qwen2-1.5b",) if quick else ("qwen2-1.5b", "granite-8b"):
        pair = {}
        for spec_on in (True, False):
            args = serve.build_parser().parse_args(
                ["--arch", arch, "--requests", "8",
                 "--max-new", "8" if quick else "24",
                 "--max-len", "384", "--shared-prefix", "256",
                 "--backend", backend, "--warmup"]
                + (["--spec-decode", "--draft-len", "12"] if spec_on
                   else ["--no-spec-decode", "--decode-horizon", "4"]))
            out = serve.run(args)
            row = {"arch": arch, **out}
            row["backend"] = "spec" if spec_on else "h4"   # A/B variable
            rows.append(row)
            pair[spec_on] = row
        sp, h4 = pair[True], pair[False]
        assert sp["tokens_fp"] == h4["tokens_fp"], \
            f"{arch}: speculative decode changed the generated tokens"
        assert sp["spec_rounds"] > 0 and sp["draft_tokens"] > 0, \
            f"{arch}: no speculative rounds ran"
        assert sp["accepted_tokens"] > 0, \
            f"{arch}: the draft never proposed an accepted token"
        speedup = sp["decode_tok_s"] / max(h4["decode_tok_s"], 1e-9)
        print(f"## {arch}: spec-decode {sp['decode_tok_s']} tok/s vs "
              f"H=4 {h4['decode_tok_s']} (x{speedup:.2f}) at acceptance "
              f"{sp['acceptance_rate']} "
              f"({sp['accepted_tokens']}/{sp['draft_tokens']} drafts), "
              f"tokens byte-identical")
    print("# serving speculative-decode A/B (8 requests, 256-token shared "
          "prefix, draft_len 12)")
    hdr = [h for h in rows[0] if h != "requests"]
    print(",".join(str(h) for h in hdr))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in hdr))
    return rows


def bench_serving_stream(quick: bool = False, backend: str = "auto"):
    """Continuous-batching A/B: stream scheduler vs static waves vs solo.

    One seeded prompt set served three ways: ``stream`` — the scheduler
    with a Poisson arrival process (requests submitted mid-run, token-
    budget admission, slots recycled in flight); ``static`` — the fixed-
    wave engine, everything submitted up front; ``solo`` — max_batch=1,
    every request alone (the isolation reference). Asserts the
    acceptance contract: queued requests really were admitted into slots
    vacated mid-run (``sched_recycled`` > 0 — with 2 slots and 3x more
    requests, admission past the first wave happens between decode
    rounds, not at a drain barrier), per-request generated tokens
    byte-identical across all three legs (``tokens_fp``: scheduling
    reorders admission, never compute), and TTFT / TPOT / queue-depth
    stats recorded on the stream row.
    """
    from repro.launch import serve

    rows = []
    for arch in ("qwen2-1.5b",) if quick else ("qwen2-1.5b", "granite-8b"):
        base = ["--arch", arch, "--requests", "6" if quick else "12",
                "--max-new", "4" if quick else "8", "--max-batch", "2",
                "--backend", backend, "--seed", "3", "--warmup"]
        legs = {}
        for name, extra in (
                ("stream", ["--stream-sched", "--arrival-rate", "0.5"]),
                ("static", []),
                ("solo", [])):
            argv = list(base)
            if name == "solo":
                argv[argv.index("--max-batch") + 1] = "1"
            out = serve.run(serve.build_parser().parse_args(argv + extra))
            row = {"arch": arch, **out}
            row["backend"] = name              # the A/B independent variable
            rows.append(row)
            legs[name] = row
        st, fx, so = legs["stream"], legs["static"], legs["solo"]
        assert st["tokens_fp"] == fx["tokens_fp"], \
            f"{arch}: stream scheduling changed the generated tokens"
        assert st["tokens_fp"] == so["tokens_fp"], \
            f"{arch}: stream tokens differ from per-request isolation"
        assert st["sched_recycled"] > 0, \
            f"{arch}: no request was admitted into a mid-run vacated slot"
        assert st["ttft_s_mean"] > 0 and st["tpot_s_mean"] >= 0 \
            and st["queue_depth_peak"] > 0, \
            f"{arch}: stream row missing TTFT/TPOT/queue-depth stats"
        print(f"## {arch}: stream {st['decode_tok_s']} tok/s vs static "
              f"{fx['decode_tok_s']} vs solo {so['decode_tok_s']}, "
              f"{st['sched_recycled']} mid-run slot recycles, TTFT mean "
              f"{st['ttft_s_mean']}s / p95 {st['ttft_s_p95']}s, TPOT "
              f"{st['tpot_s_mean']}s, queue depth peak "
              f"{st['queue_depth_peak']}, tokens byte-identical x3")
    print("# serving stream-scheduler A/B (Poisson arrivals, 2 slots)")
    hdr = [h for h in rows[0] if h != "requests"]
    print(",".join(str(h) for h in hdr))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in hdr))
    return rows


def bench_serving_kvquant(quick: bool = False, backend: str = "auto"):
    """Quantized KV pool A/B: int8 / fp8_v storage vs the fp32 oracle.

    Long-context shared-prefix workload (8 requests over a 256-token
    shared prefix, max_len 384 — the resident-cache-bound regime the
    quantized pool targets), served once per storage format through
    otherwise identical engines. Asserts the acceptance contract:

    * resident pool bytes-per-token of the quantized formats come out
      <= 0.35x the fp32 oracle's (codes + per-page scales, measured
      from the engine's dtype-aware footprint accounting);
    * decode tok/s of the int8 leg stays within a noise tolerance of
      the fp32 oracle (the in-register dequant must not cost the
      gather path its throughput);
    * greedy-logit drift under the documented gate: the same prompts
      pushed through the prefill forward under each storage format
      produce finite logits whose max abs deviation from the fp32
      leg stays below 0.9x the fp32 logit absmax. On the random-init
      reduced configs served offline the logit range is tiny and the
      top-1 token flips at perturbations far below the quantization
      step, so the gate is a deterministic sanity bound that catches
      implementation breakage (mis-applied scales, poison leaking
      into live pages) rather than an ML-quality claim; top-1
      agreement vs the oracle is reported per row. Token identity is
      therefore NOT asserted across storage formats — identity under
      any FIXED format is pinned by the serving suites and
      tests/test_kv_quant.py.
    """
    import numpy as np

    from repro.attention import AttnSpec
    from repro.configs import get_config
    from repro.configs.base import reduced
    from repro.launch import serve
    from repro.models import registry
    from repro.serving import Engine

    rows = []
    tol = 0.5 if quick else 0.35
    for arch in ("qwen2-1.5b",) if quick else ("qwen2-1.5b", "granite-8b"):
        legs = {}
        for dt in ("int8", "fp8_v", "fp32"):
            args = serve.build_parser().parse_args(
                ["--arch", arch, "--requests", "8",
                 "--max-new", "4" if quick else "8",
                 "--max-len", "384", "--shared-prefix", "256",
                 "--backend", backend, "--kv-dtype", dt, "--warmup"])
            out = serve.run(args)
            row = {"arch": arch, **out}
            rows.append(row)
            legs[dt] = row

        # resident footprint: the tentpole claim of the quantized pool
        fp32 = legs["fp32"]
        for dt in ("int8", "fp8_v"):
            ratio = legs[dt]["cache_bytes_per_token"] \
                / fp32["cache_bytes_per_token"]
            assert ratio <= 0.35, \
                (f"{arch}: {dt} pool {legs[dt]['cache_bytes_per_token']} "
                 f"B/token is x{ratio:.2f} of fp32 "
                 f"{fp32['cache_bytes_per_token']} (> 0.35 tolerated)")
        assert legs["int8"]["decode_tok_s"] \
            >= fp32["decode_tok_s"] * (1 - tol), \
            (f"{arch}: int8 decode {legs['int8']['decode_tok_s']} tok/s "
             f"fell more than {tol:.0%} below the fp32 oracle "
             f"{fp32['decode_tok_s']}")

        # greedy-logit drift probe: one prefill forward per format over
        # the same seeded long-context prompts (this is exactly the
        # computation the paged engines run at prefill time — the
        # round-trip gates on AttnSpec.kv_dtype alone)
        cfg = reduced(get_config(arch))
        eng = Engine(cfg, max_batch=1, max_len=32)     # params only
        rng = np.random.default_rng(0)
        toks = rng.integers(1, cfg.vocab_size, size=(4, 288))
        logit = {}
        for dt in ("fp32", "int8", "fp8_v"):
            cache = registry.init_cache(cfg, 4, max_len=288)
            lg, _, _ = registry.apply_prefill(
                cfg, eng.params, {"tokens": toks}, cache,
                attn=AttnSpec(kv_dtype=dt))
            logit[dt] = np.asarray(lg[:, -1])
        ref = logit["fp32"]
        assert np.isfinite(ref).all(), f"{arch}: fp32 logits not finite"
        gate = 0.9 * float(np.abs(ref).max())
        for dt in ("int8", "fp8_v"):
            assert np.isfinite(logit[dt]).all(), \
                f"{arch}: {dt} prefill logits not finite (poison leak?)"
            drift = float(np.abs(logit[dt] - ref).max())
            agree = float((logit[dt].argmax(-1) == ref.argmax(-1)).mean())
            assert drift <= gate, \
                (f"{arch}: {dt} greedy-logit drift {drift:.4f} exceeds "
                 f"the documented gate {gate:.4f} (0.9x fp32 absmax)")
            legs[dt]["logit_drift"] = round(drift, 4)
            legs[dt]["logit_drift_gate"] = round(gate, 4)
            legs[dt]["oracle_top1_agree"] = round(agree, 4)
            print(f"## {arch} {dt}: {legs[dt]['cache_bytes_per_token']} "
                  f"B/token vs fp32 {fp32['cache_bytes_per_token']} "
                  f"(x{legs[dt]['cache_bytes_per_token'] / fp32['cache_bytes_per_token']:.2f}), "
                  f"decode {legs[dt]['decode_tok_s']} tok/s vs "
                  f"{fp32['decode_tok_s']}, logit drift {drift:.4f} "
                  f"(gate {gate:.4f}), top-1 agree {agree:.2f}")
    print("# serving kv-quant A/B (8 requests, 256-token shared prefix, "
          "int8/fp8_v pool vs fp32 oracle)")
    hdr = [h for h in rows[-1] if h != "requests"]
    print(",".join(str(h) for h in hdr))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in hdr))
    return rows


def bench_serving_tp(quick: bool = False, backend: str = "auto"):
    """Tensor-parallel serving A/B: TP=1 vs 2 vs 4 over the sharded pool.

    The workload is the stream arch with MHA head counts that divide by
    4 (olmoe-1b-7b reduced: 4 KV heads), served through otherwise
    identical paged engines at ``--tp 1/2/4``. Mesh legs beyond the
    available device count are skipped with a loud note (CPU hosts need
    XLA_FLAGS=--xla_force_host_platform_device_count=4 exported BEFORE
    the process starts — jax fixes the device count at backend init).
    Asserts the acceptance contract per sharded leg vs TP=1:

    * byte-identical generated tokens (``tokens_fp``) — the decode
      all-gather concatenates exact per-shard head outputs, it never
      float-reduces, so TP is a pure layout transform;
    * per-shard resident pool bytes == total pool bytes / TP (the pool
      is sharded along kv-heads, never replicated);
    * decode step time within a generous noise margin of the TP=1 leg
      (host-CPU meshes simulate devices on shared cores, so the gate
      only catches collapses, not the real-accelerator scaling claim).
    """
    import jax

    from repro.launch import serve

    ndev = len(jax.devices())
    arch = "olmoe-1b-7b"   # reduced: MHA, 4 kv heads -> tp in {1, 2, 4}
    degrees = [tp for tp in (1, 2, 4) if tp <= ndev]
    if len(degrees) < 3:
        print(f"!! serving_tp: only {ndev} jax device(s) visible; running "
              f"tp={degrees} and skipping the rest (export XLA_FLAGS="
              f"--xla_force_host_platform_device_count=4 for the full A/B)")
    rows, legs = [], {}
    for tp in degrees:
        args = serve.build_parser().parse_args(
            ["--arch", arch, "--requests", "4" if quick else "8",
             "--max-new", "8" if quick else "24",
             "--layout", "paged", "--backend", backend,
             "--tp", str(tp), "--warmup"])
        out = serve.run(args)
        row = {"arch": arch, "hdp": True, **out}
        row["backend"] = f"tp{tp}"         # the A/B independent variable
        rows.append(row)
        legs[tp] = row
    base = legs[1]
    for tp in degrees[1:]:
        r = legs[tp]
        assert r["tokens_fp"] == base["tokens_fp"], \
            f"{arch}: tp={tp} changed the generated tokens"
        assert r["cache_bytes_pool_per_shard"] * tp \
            == r["cache_bytes_pool"], \
            (f"{arch}: tp={tp} per-shard pool "
             f"{r['cache_bytes_pool_per_shard']}B x{tp} != total "
             f"{r['cache_bytes_pool']}B — pool not evenly sharded")
        if r.get("meas_decode_step_s") and base.get("meas_decode_step_s"):
            # host-CPU meshes time-slice the simulated devices onto the
            # same cores, so sharded steps measure slower, not faster —
            # the gate is a collapse-catcher (shard_map retrace loops,
            # accidental full-pool gathers), not a scaling assertion
            assert r["meas_decode_step_s"] \
                <= base["meas_decode_step_s"] * 5.0, \
                (f"{arch}: tp={tp} decode step "
                 f"{r['meas_decode_step_s']}s collapsed vs tp=1 "
                 f"{base['meas_decode_step_s']}s (>5x)")
        print(f"## {arch} tp={tp}: {r['decode_tok_s']} tok/s vs tp=1 "
              f"{base['decode_tok_s']}, per-shard pool "
              f"{r['cache_bytes_pool_per_shard']}B = "
              f"{r['cache_bytes_pool']}B / {tp}, mesh {r.get('mesh')}, "
              f"tokens byte-identical")
    print("# serving tensor-parallel A/B (sharded page pool, head-axis "
          "shard_map decode)")
    hdr = [h for h in rows[0] if h != "requests"]
    print(",".join(str(h) for h in hdr))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in hdr))
    return rows


#: the deterministic chaos plan driven through --fault-plan in the
#: serving_chaos A/B: a slow step, an injected pool exhaustion (deferred
#: admission), a per-slot NaN tripwire on uid 1, then replica 0 killed.
CHAOS_PLAN = "slow@0:s=0.002;exhaust@1;nan@2:uid=1;kill@3:replica=0"


def bench_serving_chaos(quick: bool = False, backend: str = "auto"):
    """Goodput-under-faults A/B: clean fleet vs deterministic chaos.

    The same seeded workload runs twice on a dp=2 stream-scheduled
    fleet: ``clean`` — no faults; ``chaos`` — ``CHAOS_PLAN`` injected
    (slow step, pool exhaustion, a NaN-poisoned slot, replica 0 killed
    mid-run). Goodput is tokens of *ok* requests per decode second.
    Asserts the fault-tolerance acceptance contract: no request is lost
    (every uid gets a typed Result — failed over, shed, or errored, but
    never silently dropped), exactly the NaN-poisoned request fails
    while its batchmates complete in full, every scheduled fault event
    fired, and replica 0 is reported dead with its work failed over.
    """
    from repro.launch import serve

    rows = []
    for arch in ("qwen2-1.5b",) if quick else ("qwen2-1.5b", "granite-8b"):
        base = ["--arch", arch, "--requests", "6" if quick else "10",
                "--max-new", "4" if quick else "8", "--max-batch", "2",
                "--backend", backend, "--seed", "3", "--dp", "2",
                "--stream-sched", "--warmup"]
        legs = {}
        for name, extra in (("clean", []),
                            ("chaos", ["--fault-plan", CHAOS_PLAN])):
            out = serve.run(serve.build_parser().parse_args(base + extra))
            row = {"arch": arch, **out}
            row["backend"] = name          # the A/B independent variable
            row["faults"] = out.get("fault_plan") or "none"
            if out.get("decode_tok_s") and out["requests"]:
                ok_frac = out["requests_ok"] / out["requests"]
                row["goodput_tok_s"] = round(
                    out["decode_tok_s"] * ok_frac, 2)
            rows.append(row)
            legs[name] = row
        cl, ch = legs["clean"], legs["chaos"]
        assert cl["requests_ok"] == cl["requests"] \
            and cl["requests_lost"] == 0, \
            f"{arch}: clean leg dropped requests: {cl}"
        assert ch["requests_lost"] == 0, \
            (f"{arch}: chaos leg lost {ch['requests_lost']} request(s) — "
             "failover/shed must always leave a typed Result")
        assert ch["requests_failed"] == 1 \
            and ch["requests_ok"] == ch["requests"] - 1, \
            (f"{arch}: chaos leg expected exactly the NaN-poisoned "
             f"request to fail: ok={ch['requests_ok']} "
             f"failed={ch['requests_failed']} of {ch['requests']}")
        assert ch["faults_fired"] == len(CHAOS_PLAN.split(";")), \
            (f"{arch}: only {ch['faults_fired']} of the scheduled fault "
             "events fired — the plan never fully exercised the fleet")
        assert ch["replica_health"] == ["dead", "up"] \
            and ch["failovers"] == 1 and ch["requests_failed_over"] > 0, \
            (f"{arch}: replica-0 kill not reflected: "
             f"health={ch['replica_health']} failovers={ch['failovers']} "
             f"failed_over={ch['requests_failed_over']}")
        print(f"## {arch}: goodput {ch.get('goodput_tok_s')} tok/s under "
              f"chaos vs {cl.get('goodput_tok_s')} clean, "
              f"{ch['requests_ok']}/{ch['requests']} ok, "
              f"{ch['requests_failed_over']} failed over after replica-0 "
              f"kill, {ch['faults_fired']} fault events fired")
    print("# serving fault-tolerance A/B (dp=2 stream fleet, "
          f"plan {CHAOS_PLAN})")
    hdr = [h for h in rows[0] if h != "requests"]
    print(",".join(str(h) for h in hdr))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in hdr))
    return rows


BENCHES = {}


def _register():
    from benchmarks import (approximation, block_pruning, decode_roofline,
                            head_pruning, kernels_bench, net_pruning,
                            roofline_table)
    BENCHES.update({
        "block_pruning": block_pruning.main,
        "head_pruning": head_pruning.main,
        "approximation": approximation.main,
        "net_pruning": net_pruning.main,
        "kernels": kernels_bench.main,
        "roofline": roofline_table.main,
        "decode_roofline": decode_roofline.main,
        "serving": bench_serving,
        "serving_paged": bench_serving_paged,
        "serving_prefix": bench_serving_prefix,
        "serving_spec": bench_serving_spec,
        "serving_stream": bench_serving_stream,
        "serving_kvquant": bench_serving_kvquant,
        "serving_tp": bench_serving_tp,
        "serving_chaos": bench_serving_chaos,
    })


#: benches that accept an attention-backend selection (--backend)
_BACKEND_AWARE = ("serving", "serving_paged", "serving_prefix",
                  "serving_spec", "serving_stream", "serving_kvquant",
                  "serving_tp", "serving_chaos")


def write_bench_json(path: str, results: dict, *, quick: bool,
                     horizon: int) -> None:
    """Persist serving rows to ``path``, preserving the ``baseline`` block.

    The file tracks the decode-path perf trajectory across PRs:
    ``baseline`` is written once (the oldest recorded run, kept verbatim
    on every later write) and ``current`` is replaced per run. Rows carry
    decode_tok_s, decode_s_per_tok, cache_bytes and the achieved
    block/head/page sparsity per arch x hdp x horizon cell.
    """
    rows = []
    for name in _BACKEND_AWARE:
        for r in results.get(name) or []:
            row = {"bench": name, **r}
            if r.get("decode_tok_s"):
                row["decode_s_per_tok"] = round(1.0 / r["decode_tok_s"], 6)
            rows.append(row)
    if not rows:
        print(f"!! --json: no serving rows collected; {path} not written")
        return
    prev = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
        except (OSError, ValueError):
            prev = {}
    current = {"quick": quick, "decode_horizon": horizon, "rows": rows}
    data = {"baseline": prev.get("baseline") or current, "current": current}
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    if data["baseline"].get("quick") != quick:
        print("## baseline was recorded at a different --quick setting; "
              "tok/s comparison skipped")
    else:
        base_rows = data["baseline"].get("rows", [])

        def key(r):  # backend disambiguates serving_paged's layout A/B rows
            return (r.get("arch"), r.get("hdp"), r.get("bench"),
                    r.get("backend"))

        by_h = {}
        for r in rows:
            for b in base_rows:
                if key(b) == key(r) and b.get("decode_tok_s") \
                        and r.get("decode_tok_s"):
                    # baseline rows are per-token (horizon 1); grouping
                    # current rows by their horizon makes the fused-loop
                    # speedup vs the per-token baseline explicit
                    by_h.setdefault(r.get("decode_horizon", 1), []).append(
                        r["decode_tok_s"] / b["decode_tok_s"])
                    break
        for h in sorted(by_h):
            pairs = by_h[h]
            print(f"## decode tok/s vs baseline (horizon={h}): "
                  f"x{sum(pairs)/len(pairs):.2f} "
                  f"(mean over {len(pairs)} comparable rows)")
    print(f"## wrote {path}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweeps / fewer eval batches")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--backend", default="auto",
                    help="attention backend name/tag for the serving "
                         "benches; the resolved (post-fallback) backend is "
                         "recorded per output row")
    ap.add_argument("--horizon", type=int, default=4,
                    help="fused decode horizon for the serving benches "
                         "(each arch also records a horizon=1 row for the "
                         "per-token A/B)")
    ap.add_argument("--json", nargs="?", const=BENCH_JSON, default=None,
                    metavar="PATH",
                    help="write serving rows to PATH (default "
                         "BENCH_serving.json at the repo root), preserving "
                         "the recorded baseline block")
    args = ap.parse_args(argv)
    from repro.launch.runtime import check_platform, enable_compile_cache

    check_platform()
    enable_compile_cache()
    _register()
    names = list(BENCHES) if not args.only else args.only.split(",")
    failures = []
    results = {}
    for name in names:
        if name not in BENCHES:
            print(f"!! unknown benchmark {name}; have {sorted(BENCHES)}")
            failures.append(name)
            continue
        t0 = time.time()
        print(f"\n===== {name} =====", flush=True)
        kw = {"quick": args.quick}
        if name in _BACKEND_AWARE:
            kw["backend"] = args.backend
        if name == "serving":
            kw["horizon"] = args.horizon
        try:
            results[name] = BENCHES[name](**kw)
            print(f"===== {name} done in {time.time()-t0:.0f}s =====",
                  flush=True)
        except Exception:  # noqa: BLE001 — keep the harness going
            failures.append(name)
            traceback.print_exc()
    if args.json:
        write_bench_json(args.json, results, quick=args.quick,
                         horizon=args.horizon)
    if failures:
        print(f"\nFAILED: {failures}")
        return 1
    print("\nall benchmarks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
