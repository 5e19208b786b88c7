"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``: model sizes, deployment, weights) under
a traffic mix (``bench/traffic/<mix>.json``). The run builds the serving
engine (``repro.serving.Engine``) with the configuration's deployment and
every other option at its default, makes the weights on the device from
the seed, warms up every shape the cell's traffic uses, fills the engine
to steady state where the mix asks for it, and then drives the engine for
``--seconds`` from the mix's clients. Set-up (``setup_s``) is the time
from process start to the window's opening.

A cell's ``chips`` is 1 or 4, and is its configuration's
``deployment.chips``. A four-chip cell runs as one tensor-parallel engine
over its chips: the engine gets the serving mesh ``(data=1, model=4)``
(``repro.launch.mesh.make_serving_mesh``), and the weights are made on
that mesh in the program's own tensor-parallel layout (``bench/weights.py``),
with the values a one-chip make gives. The reference stays on one
device: it moves the embedding, each layer and the LM head there in turn.
A one-chip cell gets no mesh.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1`` is a
run of its own with the engine's HDP statistics on: it profiles the
window (at most ``TRACE_S`` seconds of it) and reports the per-layer
metrics, each read by ``bench/metrics/<name>.py`` from the reduced trace
and the harness's counts, with the device's busy time and a breakdown.
With ``BENCH_KEEP_TRACE=<file>`` it also writes the window's first
quarter second of the trace's record there (how the recorded trace that
``tests/bench`` reduces was made).

After the window the engine is freed and the plain float32 reference
(``bench/reference``) recomputes a sample of the served requests from the
seed; ``correct`` says whether the served tokens' logits lie, on the mean
over every compared position, within the cell's limit
(``bench/limits/<cell>.json``) of the reference's best. ``bench/control.py``
puts the control (the reference one precision step down) in the served
tokens' place through the same comparison.

The last line of standard output is the result: one JSON object. A run
off the TPU, on fewer chips than the cell asks for, or on a device the
peaks table does not know, prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench.traffic import Traffic  # noqa: E402

#: longest stretch of a traced run's window that is profiled (s)
TRACE_S = 8.0
#: a request due in the window that has not finished this long after
#: the window closed counts as failed; a closed loop drains at most this
#: long for the check's sample
DRAIN_CAP_S = 60.0
#: compiled-program names by what they do (substrings of XLA module names)
PROGRAMS = {"decode": ["_decode_loop_paged_fn"],
            "prefill": ["_prefill_paged_fn", "_prefill_chunk_fn", "_insert_fn"]}
KERNELS = ["hdp_paged_fum_decode"]
#: the most requests submitted between two engine steps (an open loop's
#: arrivals that fall due together wait for the next step beyond it); the
#: warm-up compiles every prefill group size up to it
SUBMIT_CAP = 4
#: the chips a cell may ask for: one, or one host's four as one
#: tensor-parallel engine
CHIPS = (1, 4)


class NoChip(RuntimeError):
    """The run is not on the chips the cell asks for."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ cell
@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: dict


def _for_cell(metrics: List[dict], name: str) -> List[dict]:
    return [m for m in metrics if "workloads" not in m or name in m["workloads"]]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"unknown workload {name!r}")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{wl['traffic']}.json")
                     .read_text())
    limits = json.loads((root / "bench" / "limits" / f"{name}.json").read_text())
    chips = int(wl["chips"])
    if chips not in CHIPS:
        raise SystemExit(f"{name}: chips {chips}; a cell runs on one of {CHIPS}")
    if chips != config["deployment"]["chips"]:
        raise SystemExit(f"{name}: chips {chips}, but its configuration "
                         f"{wl['config']!r} is deployed on "
                         f"{config['deployment']['chips']}")
    if config["num_key_value_heads"] % chips:
        raise SystemExit(f"{name}: {chips} chips do not divide the "
                         f"{config['num_key_value_heads']} kv heads "
                         f"(tensor parallelism shards them)")
    return Cell(name, chips, config, mix,
                _for_cell(spec["end_to_end"], name),
                _for_cell(spec["per_layer"], name), limits)


def load_reader(metric: str, root: Path = ROOT):
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str, root: Path = ROOT) -> dict:
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def check_devices(chips: int):
    """The first ``chips`` TPU devices; raises NoChip otherwise."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, {len(devs)} found")
    return devs[:chips]


def peak_bytes(devices) -> int:
    """The peak of device memory in use on the fullest of ``devices``
    (0 where the backend does not report it)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# ------------------------------------------------------------ statistics
def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile; a failed request is +inf."""
    v = sorted(values)
    if not v:
        return math.inf
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


# ---------------------------------------------------------------- engine
def build_engine(cell: Cell, seed: int, collect_stats: bool, devices):
    """The engine of ``cell`` on ``devices`` (its chips), with the seed's
    weights: one chip unsharded, four as one tensor-parallel engine on the
    serving mesh (data=1, model=chips)."""
    import jax
    from repro.launch.mesh import make_serving_mesh
    from repro.models import registry
    from repro.serving import Engine

    from bench.weights import make_params, program_config

    cfg = program_config(cell.config)
    mesh = (make_serving_mesh(tp=cell.chips, devices=devices)
            if cell.chips > 1 else None)
    params = make_params(cell.config, seed, mesh)
    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        registry.abstract_params(cfg)[0])
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise RuntimeError("benchmark weights do not match the program's "
                           "parameter layout")
    jax.block_until_ready(params)
    log(f"weights made: peak bytes per chip {peak_bytes(devices)}")
    dep = cell.config["deployment"]
    eng = Engine(cfg, params=params, max_batch=dep["max_batch"],
                 max_len=dep["max_len"],
                 prefill_buckets=tuple(dep["prefill_buckets"]),
                 collect_stats=collect_stats, mesh=mesh)
    if eng.kv_dtype != dep["kv_pool"]["dtype"] or \
            eng.pages.page_size != dep["page_size"]:
        raise RuntimeError(f"engine pool {eng.kv_dtype}/{eng.pages.page_size}"
                           f" differs from the deployment {dep['kv_pool']}")
    return eng


# --------------------------------------------------------------- driving
class Driver:
    """Drives one engine from one mix; keeps per-request host records."""

    def __init__(self, eng, config: dict, cap: int, account: bool):
        from repro.serving import Request

        self.Request = Request
        self.eng, self.config, self.cap = eng, config, cap
        self.reqs: Dict[int, dict] = {}       # uid -> record
        self.inflight: set = set()
        self.account = account
        self.n_results = 0
        self.step_no = 0
        self.work = {"decode_flops": 0.0, "prefill_flops": 0.0,
                     "prefill_tokens": 0, "slot_step_pages": 0.0,
                     "queue_waits": [], "decode_steps": 0, "ttfts": []}
        self.active: Dict[int, dict] = {}     # uid -> record, accounting only

    def submit(self, r, uid: Optional[int] = None, due: Optional[float] = None):
        import jax
        with jax.profiler.TraceAnnotation("bench.submit"):
            uid = r.index if uid is None else uid
            self.eng.submit(self.Request(uid, r.prompt.tolist(),
                                         max_new_tokens=int(r.max_new)))
            now = time.perf_counter()
            self.reqs[uid] = {"uid": uid, "prompt": r.prompt, "plen": len(r.prompt),
                              "max_new": int(r.max_new), "due": due,
                              "submitted": now, "done": False}
            self.inflight.add(uid)

    def step(self) -> List[dict]:
        """One engine step; returns the records of requests it finished."""
        import jax
        with jax.profiler.TraceAnnotation("bench.step"):
            self.eng.step()
        self.step_no += 1
        with jax.profiler.TraceAnnotation("bench.results"):
            res = self.eng.results()
            if self.account:
                self._account(res)
            done = []
            for uid in list(self.inflight):
                r = res.get(uid)
                if r is not None and (r.status != "ok" or r.tokens):
                    rec = self.reqs[uid]
                    rec.update(done=True, finished=time.perf_counter(),
                               status=r.status, tokens=list(r.tokens),
                               ttft_s=r.ttft_s, tpot_s=r.tpot_s,
                               queue_wait_s=r.queue_wait_s)
                    self.inflight.discard(uid)
                    done.append(rec)
        return done

    def _account(self, res) -> None:
        """Model work of the step just run, from the engine's Results:
        a request activated in this step prefilled its prompt and, like
        every other active request, generated one token (horizon 1)."""
        from bench.counts import decode_token_flops, prefill_flops

        keys = list(res)
        for uid in keys[self.n_results:]:
            rec = self.reqs.get(uid)
            if rec is None:
                continue
            rec["act_step"] = self.step_no
            self.active[uid] = rec
            if self.in_window:
                self.work["prefill_flops"] += prefill_flops(self.config, rec["plen"])
                self.work["prefill_tokens"] += rec["plen"]
                if res[uid].queue_wait_s is not None:
                    self.work["queue_waits"].append(res[uid].queue_wait_s)
        self.n_results = len(keys)
        ps = self.config["deployment"]["page_size"]
        if self.in_window and self.active:
            self.work["decode_steps"] += 1
        for uid, rec in list(self.active.items()):
            if self.in_window:
                ctx = rec["plen"] + (self.step_no - rec["act_step"])
                self.work["decode_flops"] += decode_token_flops(self.config, ctx)
                self.work["slot_step_pages"] += -(-(rec["plen"] + rec["max_new"]) // ps)
            r = res[uid]
            if r.status != "ok" or r.tokens:
                del self.active[uid]

    in_window = False


def warm_up(drv: Driver, dep: dict, mix: dict, vocab: int, seed: int) -> int:
    """Serve one throwaway request per shape the cell's traffic uses:
    every prefill bucket a prompt can fall in, at every group size up to
    the harness's per-step submission cap, and every tail a chunked
    prompt can end in. Returns the number of warm-up requests."""
    rng = np.random.default_rng([int(seed), 7])
    buckets = sorted(dep["prefill_buckets"])
    big = buckets[-1]
    lo, hi = int(mix["prompt"]["lo"]), int(mix["prompt"]["hi"])
    shapes = []
    prev = 0
    for b in buckets:
        if lo <= b and hi > prev:          # some prompt pads to bucket b
            shapes += [(min(b, hi), g) for g in range(1, drv.cap + 1)]
        prev = b
    if hi > big:                           # chunked prompts: every tail
        shapes += [(big + b, 1) for b in buckets if big + b <= hi + big]
    uid = -1
    n = 0
    for plen, group in shapes:
        for _ in range(group):
            toks = rng.integers(1, vocab, size=plen).astype(np.int32)
            drv.eng.submit(drv.Request(uid, toks.tolist(), max_new_tokens=2))
            uid -= 1
            n += 1
        while drv.eng.step():
            pass
    return n


def _profile_options():
    """Device ops and the harness's spans; no Python function tracing,
    which would slow the host loop the trace is meant to show."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run_window(drv: Driver, traffic: Traffic, seconds: float, trace_dir=None):
    """Drive the window (profiled into ``trace_dir`` if given); an open
    loop then keeps its arrivals coming until every request due in the
    window has finished. Returns (t0, t_end, the engine's summary at the
    window's end, lateness)."""
    import jax

    eng = drv.eng
    lateness: List[float] = []
    if traffic.closed:
        t0 = time.perf_counter()
        if trace_dir:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profile_options())
        eng.reset_metrics()
        drv.in_window = True
        win = jax.profiler.TraceAnnotation("bench.window")
        win.__enter__()
        while time.perf_counter() - t0 < seconds:
            for _ in drv.step():
                drv.submit(traffic.next_request())
        t_end = time.perf_counter()
        summary = eng.summary()
        win.__exit__(None, None, None)
        if trace_dir:
            jax.profiler.stop_trace()
        drv.in_window = False
        return t0, t_end, summary, lateness

    # open loop: submit each request when due (at most `cap` per step)
    t0 = time.perf_counter()
    if trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
    eng.reset_metrics()
    drv.in_window = True
    win = jax.profiler.TraceAnnotation("bench.window")
    win.__enter__()
    k, t_end, summary = 0, None, None
    clock0 = t0            # the schedule's zero; moved past a trace's export
    while True:
        now = time.perf_counter() - clock0
        sent = 0
        while k < traffic.n and traffic.due[k] <= now and sent < drv.cap:
            r = traffic.request(k)
            drv.submit(r, due=clock0 + r.due)
            lateness.append(time.perf_counter() - (clock0 + r.due))
            k += 1
            sent += 1
        if t_end is None and now >= seconds:
            t_end = time.perf_counter()
            summary = eng.summary()
            win.__exit__(None, None, None)
            if trace_dir:
                jax.profiler.stop_trace()
                pause = time.perf_counter() - t_end
                clock0 += pause
                now -= pause
                log(f"trace export {pause:.3f} s: the arrivals after the "
                    f"window wait it out")
            drv.in_window = False
        if t_end is not None:
            pending = [u for u in drv.inflight if u < traffic.in_window]
            if not pending or now > seconds + DRAIN_CAP_S:
                break
        if not drv.inflight:
            nxt = traffic.due[k] if k < traffic.n else now + 0.01
            wait = max(0.0, min(nxt - now, 0.01))
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(wait)
            continue
        drv.step()
    return t0, t_end, summary, lateness


# ----------------------------------------------------------- correctness
def drain_for_check(drv: Driver, traffic: Traffic, mix: dict, seed: int,
                    min_tokens: int) -> tuple:
    """Closed loop, after the window: serve the requests still in flight,
    sending no new ones, until the check's sample holds ``min_tokens``
    served tokens or ``DRAIN_CAP_S`` has passed. A short window (a traced
    run's) finishes only the first wave's shortest remaining budgets.
    Returns (seconds drained, requests finished meanwhile)."""
    def sampled() -> int:
        return sum(len(r["tokens"])
                   for r in sample_for_check(drv, traffic, mix, seed))

    t, n = time.perf_counter(), 0
    short = sampled() < min_tokens
    while short and drv.inflight and time.perf_counter() - t < DRAIN_CAP_S:
        done = drv.step()
        n += len(done)
        short = not done or sampled() < min_tokens
    return time.perf_counter() - t, n


def sample_for_check(drv: Driver, traffic: Traffic, mix: dict,
                     seed: int) -> List[dict]:
    """Requests the window finished: the longest (prompt + served) and
    others drawn from the seed, within the mix's ``check`` budget."""
    done = [r for r in drv.reqs.values()
            if r["done"] and r["status"] == "ok" and r["uid"] >= 0
            and (traffic.closed or r["uid"] < traffic.in_window)]
    if not done:
        return []
    chk = mix["check"]
    done.sort(key=lambda r: r["uid"])
    longest = max(done, key=lambda r: r["plen"] + len(r["tokens"]))
    rng = np.random.default_rng([int(seed), 11])
    order = [done[i] for i in rng.permutation(len(done))]
    pick, budget = [longest], len(longest["tokens"])
    for r in order:
        if len(pick) >= int(chk["requests"]):
            break
        if r is longest or budget + len(r["tokens"]) > int(chk["max_served_tokens"]):
            continue
        pick.append(r)
        budget += len(r["tokens"])
    return pick


def reference_check(config: dict, seed: int, picks: List[dict],
                    control: bool = False, mesh=None) -> dict:
    """How far the served tokens of ``picks`` lie below the float32
    reference's best logit, position by position: ``mean_gap`` (the
    number compared: the mean over every compared position), with the
    widest gap and the share of positions whose token is not the
    reference's first beside it.

    With ``control`` the control takes the program's place: at each
    served position the token that the reference one precision step
    below the configuration's dtype puts first replaces the served token,
    and the numbers are the control's; the program's are kept beside
    them under ``program``.

    The weights are made again on the engine's ``mesh`` (None: one
    device); the reference computes on one device whatever their layout."""
    import jax
    from bench.reference.model import CONTROL, served_logits
    from bench.weights import make_params

    params = make_params(config, seed, mesh)
    gaps, prog, per = [], [], []
    for r in picks:
        ctrl = None
        if control:
            ctrl = served_logits(config, params, r["prompt"], r["tokens"],
                                 precision=CONTROL[config["torch_dtype"]])["argmax"]
        ref = served_logits(config, params, r["prompt"], r["tokens"], extra=ctrl)
        p = ref["max"] - ref["served"]
        g = p if ctrl is None else ref["max"] - ref["extra"]
        gaps.append(g)
        prog.append(p)
        per.append({"uid": r["uid"], "prompt": r["plen"], "served": len(g),
                    "mean_gap": float(np.mean(g)), "widest_gap": float(np.max(g)),
                    "off_argmax": float(np.mean(g > 0))})
    del params
    jax.clear_caches()

    def summary(gs):
        g = np.concatenate(gs) if gs else np.zeros(0)
        if not len(g):
            return {"mean_gap": math.inf, "widest_gap": math.inf,
                    "off_argmax": 1.0, "tokens": 0}
        return {"mean_gap": float(np.mean(g)), "widest_gap": float(np.max(g)),
                "off_argmax": float(np.mean(g > 0)), "tokens": int(len(g))}

    out = summary(gaps)
    out["requests"] = per
    if control:
        out["program"] = summary(prog)
    return out


# ------------------------------------------------------------------ main
def _slice(rec: dict, t0: float, t1: float) -> dict:
    """A small piece of a trace record (events starting in [t0, t1))."""
    cut = lambda evs: [e for e in evs if t0 <= e[1] < t1]
    return {"window": [t0, t1], "host": cut(rec["host"]),
            "chips": [{"name": c["name"], "ops": cut(c["ops"]),
                       "modules": cut(c["modules"])} for c in rec["chips"]]}


@dataclass
class MetricContext:
    """What a per-layer metric reader may read."""

    trace: object
    summary: dict
    work: dict
    config: dict
    dims: dict
    peaks: dict
    chips: int


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        control: bool = False, devices=None, peaks=None) -> dict:
    """One run of ``cell``. ``devices``/``peaks`` are given only by the
    tests, which drive a run on the CPU; a benchmark run takes the TPU
    devices and their peaks itself. With ``control`` the comparison
    judges the control's tokens instead of the served ones."""
    import jax
    from repro.launch.runtime import enable_compile_cache

    from bench.weights import dims

    devs = devices if devices is not None else check_devices(cell.chips)
    dev = devs[0]
    peaks = peaks if peaks is not None else load_peaks(dev.device_kind)
    if devices is None:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = {"n": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    D = dims(cell.config)
    dep = cell.config["deployment"]
    eng = build_engine(cell, seed, collect_stats=trace, devices=devs)
    mesh = eng.mesh
    log(f"engine: {cell.config['model']} decode {eng.resolved_backend('decode')}"
        f" prefill {eng.resolved_backend('prefill')} kv {eng.kv_dtype}"
        f" tp {eng.tp}")
    mix = cell.mix
    drain = float(mix.get("drain_seconds", 0.0))
    window_s = min(seconds, TRACE_S) if trace else seconds
    traffic = Traffic(mix, slots=dep["max_batch"], vocab=D["V"],
                      max_len=dep["max_len"], seed=seed, seconds=window_s,
                      drain_seconds=drain)
    drv = Driver(eng, cell.config, SUBMIT_CAP, account=trace)
    n_warm = warm_up(drv, dep, mix, D["V"], seed)
    if traffic.closed:
        for r in traffic.first_wave():
            drv.submit(r)
        drv.step()                       # admits the whole first wave
        drv.step()
    jax.effects_barrier()
    drv.n_results = len(eng.results())
    compiles_setup = compiles["n"]
    trace_dir = str(ROOT / ".bench_trace") if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    t_setup = time.perf_counter() - T_START
    t0, t_end, summary, lateness = run_window(drv, traffic, window_s,
                                              trace_dir)
    tokens = summary["tokens_out"]
    red = None
    in_window_compiles = compiles["n"] - compiles_setup
    window = t_end - t0
    peak = peak_bytes(devs)
    log(f"setup_s {t_setup:.3f} (warm-up requests {n_warm}, compiles "
        f"{compiles_setup}); window {window:.3f} s; compiles in window "
        f"{in_window_compiles}; tokens {tokens}; requests finished "
        f"{sum(r['done'] for r in drv.reqs.values())} of {len(drv.reqs)}")
    if lateness:
        log(f"generator lateness: mean {np.mean(lateness) * 1e3:.3f} ms, "
            f"max {np.max(lateness) * 1e3:.3f} ms over {len(lateness)} submits")
    log(f"sparsity (engine): block {summary.get('block_sparsity')} head "
        f"{summary.get('head_sparsity')} page {summary.get('page_sparsity')}")
    if mesh is not None:
        log(f"mesh {summary.get('mesh_shape')}: tp {summary.get('tp')}, pool "
            f"bytes per shard {summary.get('cache_bytes_pool_per_shard')}")

    metrics: Dict[str, dict] = {}
    attempted = failed = 0
    errors = sum(1 for r in drv.reqs.values() if r["done"] and r["status"] != "ok")
    if traffic.closed:
        recs = [r for r in drv.reqs.values() if r["uid"] >= 0]
        attempted = sum(1 for r in recs if r["done"])
        failed = errors
    else:
        recs = [r for r in drv.reqs.values()
                if 0 <= r["uid"] < traffic.in_window]
        attempted = len(recs)
        ttft, tpot = [], []
        for r in recs:
            ok = r["done"] and r["status"] == "ok" and r["ttft_s"] is not None
            failed += 0 if ok else 1
            ttft.append(r["submitted"] - r["due"] + r["ttft_s"] if ok else math.inf)
            tp = r.get("tpot_s") if ok else None
            tpot.append(tp if tp is not None else math.inf)
        drv.work["ttfts"] = ttft
        log(f"requests due in window {len(recs)}; ttft p50 "
            f"{percentile(ttft, 50):.4f} s; tpot p50 {percentile(tpot, 50) * 1e3:.3f} ms")
    e2e = {
        "output_tok_s": (tokens / window, "tokens/s"),
        "setup_s": (t_setup, "s"),
    }
    if not traffic.closed:
        e2e["ttft_p95_s"] = (percentile(ttft, 95), "s")
        e2e["tpot_p95_ms"] = (percentile(tpot, 95) * 1e3, "ms")

    if trace:
        from bench.trace import load_xplane, reduce

        rec = load_xplane(trace_dir)
        log(f"trace lines: { {k: v for k, v in rec['lines'].items() if 'TPU' in k} }")
        spans = [h for h in rec["host"] if h[0] == "bench.window"]
        w0 = spans[0][1] if spans else min(e[1] for e in rec["chips"][0]["ops"])
        w1 = w0 + (spans[0][2] if spans else window * 1e9)
        red = reduce(rec, (w0, w1), programs=PROGRAMS, kernels=KERNELS)
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            Path(keep).write_text(json.dumps(_slice(rec, w0, w0 + 0.25e9)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = MetricContext(red, summary, drv.work, cell.config, D, peaks,
                            cell.chips)
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        log(f"trace: window {red.window_s:.4f} s busy {red.busy_s:.4f} s "
            f"programs {red.program_s} {red.program_n} kernels {red.kernel_s}"
            f" {red.kernel_n} collectives {red.collective_s} s "
            f"({red.collective_n} on the first chip)")
    else:
        for m in cell.end_to_end:
            v, unit = e2e[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": unit}

    # ---- correctness: free the program's state, then the reference
    min_tokens = int(cell.limits["min_tokens"])
    if traffic.closed:
        before = sum(len(r["tokens"])
                     for r in sample_for_check(drv, traffic, mix, seed))
        drained, n_drained = drain_for_check(drv, traffic, mix, seed, min_tokens)
        log(f"check sample at the window's close: {before} served tokens; "
            f"drained {drained:.3f} s, {n_drained} more requests finished")
        errors = sum(1 for r in drv.reqs.values()
                     if r["done"] and r["status"] != "ok")
    picks = sample_for_check(drv, traffic, mix, seed)
    picks = [{k: r[k] for k in ("uid", "prompt", "plen", "tokens")} for r in picks]
    del eng, drv
    gc.collect()
    t_check = time.perf_counter()
    gaps = reference_check(cell.config, seed, picks, control=control,
                           mesh=mesh)
    limit = float(cell.limits["mean_gap"]["limit"])
    checked = {"mean_gap": {"value": gaps["mean_gap"], "limit": limit},
               "tokens_compared": {"value": gaps["tokens"], "limit": min_tokens},
               "request_errors": {"value": errors, "limit": 0}}
    correct = (gaps["mean_gap"] <= limit and
               gaps["tokens"] >= checked["tokens_compared"]["limit"] and
               errors == 0)
    for r in gaps["requests"]:
        log(f"check request {r}")
    log(f"check {'control' if control else 'program'}: widest gap "
        f"{gaps['widest_gap']}, off the reference's argmax {gaps['off_argmax']}"
        f" ({time.perf_counter() - t_check:.1f} s)")
    if control:
        log(f"check program beside the control: {gaps['program']}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if red is not None:
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = {"device_ops": [[n, s] for n, s in red.device_ops],
                            "idle_gaps": [[n, s] for n, s in red.idle_gaps]}
    if control:
        out["program"] = gaps["program"]
        out["check_requests"] = gaps["requests"]
    out["check"] = checked
    for k, v in checked.items():
        log(f"check {k}: {v['value']} limit {v['limit']}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
