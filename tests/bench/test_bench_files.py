"""The harness finds every cell, configuration, mix and metric by its
name in BENCHMARK.json, counts operations and bytes by hand-checkable
formulas, and refuses to run off the TPU."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import counts as C
from bench import run as R

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_named_file_exists():
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        cell = R.load_cell(w["name"])
        assert cell.config["deployment"]["chips"] == w["chips"] == cell.chips
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    for m in SPEC["per_layer"]:
        assert callable(R.load_reader(m["name"]))


def test_added_files_are_found_without_editing_the_harness(tmp_path):
    """A later change adds a configuration, a mix, a metric and a cell as
    new files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench")
    spec = json.loads(json.dumps(SPEC))
    cfg = json.loads((ROOT / "bench/configs/qwen2-1.5b.json").read_text())
    cfg["deployment"]["max_batch"] = 4
    (root / "bench/configs/new-model.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/chat-poisson.json").read_text())
    mix["rate_per_s"] = 1.5
    (root / "bench/traffic/new-mix.json").write_text(json.dumps(mix))
    (root / "bench/metrics/new_metric.x.py").write_text(
        "def read(ctx):\n    return 42.0 + ctx['n']\n")
    (root / "bench/limits/new-model.new-mix.json").write_text(
        json.dumps({"mean_gap": {"limit": 0.5}, "min_tokens": 1}))
    spec["configs"].append({"name": "new-model", "source": "x",
                            "file": "bench/configs/new-model.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "new-model.new-mix",
                              "config": "new-model", "traffic": "new-mix",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new_metric.x", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "output_tok_s",
                              "workloads": ["new-model.new-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = R.load_cell("new-model.new-mix", root=root)
    assert cell.config["deployment"]["max_batch"] == 4
    assert cell.mix["rate_per_s"] == 1.5
    assert [m["name"] for m in cell.per_layer] == ["new_metric.x"]
    assert R.load_reader("new_metric.x", root=root)({"n": 1}) == 43.0
    # metrics without a workloads list reach the new cell too
    assert {m["name"] for m in cell.end_to_end} == {"output_tok_s", "setup_s"}


def test_refuses_a_cpu_device():
    with pytest.raises(R.NoChip):
        R.check_devices(1)
    with pytest.raises(R.NoChip):
        R.load_peaks("cpu")
    assert R.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_command_prints_no_result_off_the_tpu():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "qwen2-1.5b.decode-long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def tiny():
    return json.loads((ROOT / "tests/bench/data/tiny.json").read_text())


def test_flop_counts_match_a_hand_count():
    c = tiny()   # d 64, H 4, N 2, hd 16, f 128, L 2, V 256
    per_layer = 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64 + 3 * 64 * 128
    assert C.matmul_flops_per_token(c, lm_head=False) == 2 * 2 * per_layer
    assert C.matmul_flops_per_token(c, lm_head=True) == \
        2 * (2 * per_layer + 64 * 256)
    assert C.attn_flops(c, 10) == 4 * 10 * 4 * 16 * 2
    assert C.decode_token_flops(c, 10) == \
        C.matmul_flops_per_token(c, lm_head=True) + 4 * 10 * 4 * 16 * 2
    # prompt of 3: positions attend 1, 2, 3 keys
    assert C.prefill_flops(c, 3) == \
        3 * C.matmul_flops_per_token(c, lm_head=False) + 4 * 4 * 16 * 2 * 6
    assert C.prefill_flops(c, 5, start=3) == \
        2 * C.matmul_flops_per_token(c, lm_head=False) + 4 * 4 * 16 * 2 * 9
    w = dict(c, sliding_window=2)   # window: at most 2 keys each
    assert C.prefill_flops(w, 3) == \
        3 * C.matmul_flops_per_token(w, lm_head=False) + 4 * 4 * 16 * 2 * 5
    assert C.attn_flops(w, 10) == 4 * 2 * 4 * 16 * 2


def test_kernel_cost_hand_count():
    c = tiny()   # page 128, hd 16, N 2, G 2
    b, f = C.paged_kernel_cost(c, kept_pages=3, calls=1, slots=1,
                               table_pages=8)
    tile = 128 * 16
    fixed = 1 * 2 * (2 * tile + 2 * 2 * 16 * 4 + 8 * 2 * 4) + 1 * 8 * 4 * 2
    assert b == 3 * 2 * 2 * tile + fixed
    assert f == 3 * 2 * 3 * 2 * 2 * 128 * 16
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    assert C.roofline_s(b, f, peaks) == max(b / 1e9, f / 1e12)


def test_kernel_cost_of_one_chip_of_four_hand_count():
    """Tensor parallelism over the deployment's 4 chips: one chip's kernel
    walks its own 4 // 4 = 1 kv head (query group still 8 // 4 = 2) over
    every slot's page lists."""
    c = json.loads((ROOT / "tests/bench/data/tiny_tp4.json").read_text())
    assert c["deployment"]["chips"] == 4
    kw = dict(kept_pages=3, calls=2, slots=4, table_pages=8)
    b, f = C.paged_kernel_cost(c, **kw)
    tile = 128 * 16
    fixed = 4 * 1 * (2 * tile + 2 * 2 * 16 * 4 + 8 * 2 * 4) + 4 * 8 * 4 * 2
    assert b == 3 * 1 * 2 * tile + 2 * fixed
    assert f == 3 * 1 * 3 * 2 * 2 * 128 * 16
    # the same file deployed on one chip counts all 4 kv heads
    one = dict(c, deployment=dict(c["deployment"], chips=1))
    b1, f1 = C.paged_kernel_cost(one, **kw)
    assert (b1, f1) == (3 * 4 * 2 * tile + 2 * (
        4 * 4 * (2 * tile + 2 * 2 * 16 * 4 + 8 * 2 * 4) + 4 * 8 * 4 * 2),
        3 * 4 * 3 * 2 * 2 * 128 * 16)


def test_kernel_byte_count_matches_the_tiles_the_kernel_fetches(monkeypatch):
    """At a small size in interpret mode: the kept pages the benchmark
    derives from the engine's page sparsity are the pages the paged
    decode kernel is handed, and its byte count is the tiles the kernel's
    block index walks over (a tile is fetched when the index changes)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.kernels.hdp_paged_decode as K
    from repro.core.config import HDPConfig
    from repro.models.attention import hdp_paged_decode_attention

    B, N, G, hd, ps, nP = 2, 2, 2, 16, 128, 4
    P = 1 + B * nP
    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.integers(-60, 60, (P, N, ps, hd)), jnp.int8)
    vp = jnp.asarray(rng.integers(-60, 60, (P, N, ps, hd)), jnp.int8)
    scale = jnp.full((P, N), 0.125, jnp.float32)
    table = jnp.asarray([[1, 2, 3, 0], [5, 6, 7, 8]], jnp.int32)
    pos = jnp.asarray([300, 500], jnp.int32)
    ar = jnp.arange(nP * ps)
    k_pos = jnp.where(ar[None] <= pos[:, None], ar, -1)[:, None, None, :]
    q = 2.0 * jax.random.normal(jax.random.PRNGKey(1), (B, N, G, 1, hd))
    hdp = HDPConfig(block_q=ps, block_k=ps, causal=True,
                    normalize_head_score=True, calib="none")
    seen = {}
    real = K.hdp_paged_fum_decode

    def spy(qq, k_pool, v_pool, page_ids, logical, counts, keep, kv_len,
            **kw):
        seen.update(page_ids=np.asarray(page_ids), counts=np.asarray(counts))
        return real(qq, k_pool, v_pool, page_ids, logical, counts, keep,
                    kv_len, **kw)

    monkeypatch.setattr(K, "hdp_paged_fum_decode", spy)
    out, stats = hdp_paged_decode_attention(
        q, kp, vp, None, table, q_pos=pos[:, None, None, None], k_pos=k_pos,
        hdp=hdp, return_stats=True, stage3="pallas_paged", k_scale=scale,
        v_scale=scale)
    assert np.isfinite(np.asarray(out)).all()
    alloc = np.asarray((table > 0).sum(-1))
    kept = (1 - np.asarray(stats["page_sparsity"])) * alloc
    counts = seen["counts"]
    assert np.allclose(kept, counts) and counts.sum() > 0
    # walk the kernel's grid (b, n, j): K and V tiles fetched whenever the
    # page index changes between consecutive grid steps
    tiles, last = 0, None
    for b in range(B):
        for n in range(N):
            for j in range(nP):
                pid = seen["page_ids"][b, j] if j < counts[b] else 0
                if (b, n, pid) != last:
                    tiles += 1
                last = (b, n, pid)
    cfg = dict(tiny(), num_key_value_heads=N, num_attention_heads=N * G,
               head_dim=hd)
    b_, _ = C.paged_kernel_cost(cfg, kept_pages=float(counts.sum()), calls=1,
                                slots=B, table_pages=nP)
    fixed = B * N * (2 * G * hd * 4 + nP * G * 4) + B * nP * 4 * 2
    assert b_ == tiles * 2 * ps * hd + fixed


def _checkout(tmp_path, chips, kv_heads, deployed):
    """A checkout whose one cell asks for ``chips`` on a configuration of
    ``kv_heads`` kv heads deployed on ``deployed`` chips."""
    root = tmp_path / "checkout"
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True)
    cfg = json.loads((ROOT / "bench/configs/qwen2-1.5b.json").read_text())
    cfg["num_key_value_heads"] = kv_heads
    cfg["num_attention_heads"] = 4 * kv_heads
    cfg["deployment"]["chips"] = deployed
    (root / "bench/configs/m.json").write_text(json.dumps(cfg))
    shutil.copy(ROOT / "bench/traffic/decode-long.json", root / "bench/traffic")
    shutil.copy(ROOT / "bench/limits/qwen2-1.5b.decode-long.json",
                root / "bench/limits/m.decode-long.json")
    spec = dict(SPEC, configs=[dict(SPEC["configs"][0], name="m",
                                    file="bench/configs/m.json")],
                workloads=[{"name": "m.decode-long", "config": "m",
                            "traffic": "decode-long", "chips": chips,
                            "why": "x"}])
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.mark.parametrize("chips, kv_heads, deployed, refusal", [
    (2, 8, 2, "chips 2"),                    # neither one chip nor a host's four
    (4, 2, 4, "do not divide"),              # 4 chips cannot shard 2 kv heads
    (4, 8, 1, "deployed on 1"),              # the configuration says one chip
])
def test_load_cell_refuses_a_cell_it_cannot_run(tmp_path, chips, kv_heads,
                                                deployed, refusal):
    root = _checkout(tmp_path, chips, kv_heads, deployed)
    with pytest.raises(SystemExit, match=refusal):
        R.load_cell("m.decode-long", root=root)


def test_load_cell_takes_a_four_chip_cell(tmp_path):
    root = _checkout(tmp_path, 4, 8, 4)
    assert R.load_cell("m.decode-long", root=root).chips == 4


def test_one_chip_engine_gets_no_mesh(monkeypatch):
    import jax

    import repro.serving as S

    seen = {}
    real = S.Engine

    def engine(*a, **kw):
        seen.update(kw)
        return real(*a, **kw)

    monkeypatch.setattr(S, "Engine", engine)
    cell = R.Cell("tiny", 1, tiny(), {}, [], [], {})
    eng = R.build_engine(cell, 5, collect_stats=False,
                         devices=jax.devices()[:1])
    assert seen["mesh"] is None
    assert eng.mesh is None and eng.tp == 1
    assert all(len(a.devices()) == 1 for a in jax.tree.leaves(eng.params))
