"""Mesh-sharded serving: tensor-parallel engines + data-parallel replicas.

Load-bearing guarantees pinned here:

* TP is a pure layout transform: an engine serving over a mesh-sharded
  page pool (int8 codes + per-page scales split along kv heads, decode
  dispatched through shard_map on the "model" axis) generates tokens
  BYTE-IDENTICAL to the unsharded engine — at TP=2 and TP=4, composed
  with the fused decode loop, speculative decode, the prefix cache and
  the stream scheduler;
* the pool really is sharded, never replicated: per-shard resident
  bytes == total pool bytes / TP, and every pool leaf carries a
  NamedSharding that splits its kv-head axis across "model";
* the FUM/no-DMA contract holds per shard: NaN-poisoning free pages of
  the SHARDED pool (both sentinel channels) cannot change a token;
* mesh resolution: ``tp=`` (kwarg or the REPRO_MESH_TP env default)
  must divide the kv heads, suit the layout and fit the device count,
  or the engine raises; a ``mesh=`` disagreeing with ``tp=`` errors;
* DP replicas behind ``ReplicaSet`` share one params tree, dispatch by
  prefix affinity then least-loaded, and their merged stream yields
  the same tokens the single engine produces.

The whole module needs a multi-device host: run under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (CI's mesh legs
export it; single-device runs skip).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import AttnSpec
from repro.configs import get_config
from repro.configs.base import reduced
from repro.launch.mesh import make_serving_mesh
from repro.serving import Engine, ReplicaSet, Request

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 devices: export "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4")


def _cfg(arch="qwen2-1.5b", calib="none"):
    cfg = reduced(get_config(arch))
    return cfg.replace(hdp=cfg.hdp.replace(enabled=True, calib=calib))


def _prompts(n, lo=4, hi=24, seed=0, vocab=250, shared=0):
    rng = np.random.default_rng(seed)
    pre = rng.integers(1, vocab, size=shared).tolist()
    return [pre + rng.integers(1, vocab,
                               size=int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def _serve(cfg, params, prompts, *, max_new=5, **kw):
    eng = Engine(cfg, params=params, max_batch=2, max_len=96,
                 prefill_buckets=(16, 32, 64), **kw)
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid, p, max_new_tokens=max_new))
    res = eng.run()
    return eng, {u: r.tokens for u, r in res.items()}


# --------------------------------------------------------- mesh construction
def test_make_serving_mesh_shape():
    mesh = make_serving_mesh(tp=2)
    assert dict(mesh.shape) == {"data": 1, "model": 2}
    mesh = make_serving_mesh(tp=2, dp=2)
    assert dict(mesh.shape) == {"data": 2, "model": 2}
    with pytest.raises(RuntimeError, match="device"):
        make_serving_mesh(tp=64, dp=64)
    with pytest.raises(ValueError):
        make_serving_mesh(tp=0)


def test_engine_tp_validation():
    cfg = _cfg()
    with pytest.raises(ValueError, match="divisible"):
        Engine(cfg, max_batch=1, max_len=32, tp=3)     # 2 kv heads % 3
    with pytest.raises(ValueError, match="paged"):
        Engine(cfg, max_batch=1, max_len=32, tp=2,
               attn=AttnSpec(layout="dense"))
    mesh = make_serving_mesh(tp=2)
    with pytest.raises(ValueError, match="model axis"):
        Engine(cfg, max_batch=1, max_len=32, tp=1, mesh=mesh)


def test_env_default_raises_when_unhonourable(monkeypatch):
    cfg = _cfg()
    monkeypatch.setenv("REPRO_MESH_TP", "2")
    assert Engine(cfg, max_batch=1, max_len=32).tp == 2
    # a setting the engine cannot honour is refused, never served at tp=1
    monkeypatch.setenv("REPRO_MESH_TP", "3")
    with pytest.raises(ValueError, match="divisible"):
        Engine(cfg, max_batch=1, max_len=32)
    monkeypatch.setenv("REPRO_MESH_TP", "2")
    with pytest.raises(ValueError, match="paged"):
        Engine(cfg, max_batch=1, max_len=32, attn=AttnSpec(layout="dense"))
    monkeypatch.setenv("REPRO_MESH_TP", "8")
    with pytest.raises(RuntimeError, match="devices"):
        Engine(cfg.replace(n_kv_heads=8, n_heads=8), max_batch=1,
               max_len=32)
    # explicit kwarg wins over the env
    monkeypatch.delenv("REPRO_MESH_TP")
    assert Engine(cfg, max_batch=1, max_len=32, tp=2).tp == 2


# ------------------------------------------------------------- byte identity
@pytest.mark.parametrize("tp,arch", [(2, "qwen2-1.5b"),
                                     (4, "olmoe-1b-7b")])
def test_tp_byte_identity(tp, arch):
    """Sharded decode must not change a single token — the all-gather
    concatenates exact per-shard head outputs, it never float-reduces."""
    cfg = _cfg(arch)
    prompts = _prompts(4, seed=3)
    eng, ref = _serve(cfg, None, prompts)
    eng_tp, got = _serve(cfg, eng.params, prompts, tp=tp)
    assert got == ref, f"tp={tp} changed the generated tokens"
    assert eng_tp.tp == tp and dict(eng_tp.mesh.shape)["model"] == tp


@pytest.mark.parametrize("feat", [
    {"decode_horizon": 4},
    {"spec_decode": True, "draft_len": 3},
    {"prefix_cache": True},
    {"stream_sched": True},
    pytest.param({"decode_horizon": 4, "spec_decode": True,
                  "prefix_cache": True, "stream_sched": True},
                 id="everything-on", marks=pytest.mark.slow),
])
def test_tp2_composes_with_serving_features(feat):
    cfg = _cfg()
    shared = 32 if feat.get("prefix_cache") else 0
    prompts = _prompts(4, seed=5, shared=shared)
    eng, ref = _serve(cfg, None, prompts, **feat)
    _, got = _serve(cfg, eng.params, prompts, tp=2, **feat)
    assert got == ref, f"tp=2 + {feat} changed the generated tokens"


# ------------------------------------------------------------- pool sharding
def test_pool_sharded_not_replicated():
    cfg = _cfg()
    eng = Engine(cfg, max_batch=2, max_len=64, prefill_buckets=(16, 32),
                 tp=2)
    assert eng.pages.pool_bytes_per_shard() * 2 == eng.pages.pool_bytes()
    from repro.distribution.tp import POOL_HEAD_AXIS
    for name, leaf in eng.pages.cache.items():
        ax = POOL_HEAD_AXIS[name]
        shardings = leaf.sharding.spec
        assert shardings[ax] == "model", \
            f"{name}: head axis {ax} not sharded over 'model' ({shardings})"
        assert leaf.shape[ax] == cfg.n_kv_heads


def test_summary_reports_mesh():
    cfg = _cfg()
    eng = Engine(cfg, max_batch=2, max_len=64, prefill_buckets=(16, 32),
                 tp=2)
    eng.submit(Request(0, _prompts(1, seed=1)[0], max_new_tokens=4))
    eng.run()
    m = eng.summary()
    assert m["tp"] == 2
    assert m["mesh_shape"] == {"data": 1, "model": 2}
    assert m["cache_bytes_pool_per_shard"] * 2 == m["cache_bytes_pool"]
    assert m["collective_bytes_per_layer"] > 0


def test_poisoned_free_pages_never_read_per_shard():
    """The no-DMA contract holds on the SHARDED pool: free pages of both
    shards NaN-poisoned through both sentinel channels, tokens
    unchanged (decode gathers only table-mapped pages on each shard)."""
    from repro.core.quant import POISON_CODE

    cfg = _cfg()
    prompts = _prompts(2, seed=7)
    eng, clean = _serve(cfg, None, prompts)

    eng2 = Engine(cfg, params=eng.params, max_batch=2, max_len=96,
                  prefill_buckets=(16, 32, 64), tp=2)
    for uid, p in enumerate(prompts):
        eng2.submit(Request(uid, p, max_new_tokens=5))
    eng2.step()                        # admit + first decode
    free = list(eng2.pages._free)
    assert free, "test needs unallocated pages"
    c = eng2.pages.cache
    idx = jnp.asarray(free)
    eng2.pages.cache = {
        **c,
        "k_pages": c["k_pages"].at[:, idx].set(POISON_CODE),
        "v_pages": c["v_pages"].at[:, idx].set(POISON_CODE),
        "k_scale": c["k_scale"].at[:, idx].set(jnp.nan),
        "v_scale": c["v_scale"].at[:, idx].set(jnp.nan),
    }
    res = eng2.run()
    got = {u: r.tokens for u, r in res.items()}
    assert got == clean, "NaN leaked from never-referenced sharded pages"


# ---------------------------------------------------------------- replicas
def test_replicaset_byte_identity_and_affinity():
    cfg = _cfg()
    prompts = _prompts(6, seed=9, shared=16)
    eng, ref = _serve(cfg, None, prompts, prefix_cache=True)

    rs = ReplicaSet.build(cfg, 2, params=eng.params, max_batch=2,
                          max_len=96, prefill_buckets=(16, 32, 64),
                          prefix_cache=True)
    homes = {}
    got = {}
    for uid, p in enumerate(prompts):
        homes[uid] = rs.submit(Request(uid, p, max_new_tokens=5))
    for r in rs.serve():
        got[r.uid] = r.tokens
    assert got == ref, "replica dispatch changed the generated tokens"
    # every prompt shares a 16-token prefix: once replica 0 has served
    # the first request, affinity must route the rest to the replica
    # holding the cached prefix pages
    assert len(set(id(e) for e in homes.values())) >= 1
    counts = rs.summary()["requests_per_replica"]
    assert sum(counts) == len(prompts)
    s = rs.summary()
    # tp reflects each replica's engine (1 here, unless the
    # REPRO_MESH_TP CI leg shards them — identity holds either way)
    assert s["dp"] == 2 and s["tp"] == rs.engines[0].tp


def test_replicaset_dp2_tp2_compose():
    cfg = _cfg()
    prompts = _prompts(4, seed=11)
    eng, ref = _serve(cfg, None, prompts)
    rs = ReplicaSet.build(cfg, 2, params=eng.params, max_batch=2,
                          max_len=96, prefill_buckets=(16, 32, 64), tp=2)
    got = {r.uid: r.tokens
           for r in rs.serve([Request(u, p, max_new_tokens=5)
                              for u, p in enumerate(prompts)])}
    assert got == ref, "dp=2 x tp=2 changed the generated tokens"
    s = rs.summary()
    assert s["dp"] == 2 and s["tp"] == 2
    assert s["mesh_shape"] == {"data": 1, "model": 2}
    assert s["cache_bytes_pool_per_shard"] * 2 \
        == rs.engines[0].pages.pool_bytes()


def test_replicas_placed_on_distinct_devices():
    """dp=4 on a 4-device host: replica i holds its params and page pool
    on device i, and the fleet's tokens equal the single engine's."""
    cfg = _cfg()
    prompts = _prompts(4, seed=15)
    eng, ref = _serve(cfg, None, prompts)
    rs = ReplicaSet.build(cfg, 4, params=eng.params, max_batch=2,
                          max_len=96, prefill_buckets=(16, 32, 64), tp=1)
    got = {r.uid: r.tokens
           for r in rs.serve([Request(u, p, max_new_tokens=5)
                              for u, p in enumerate(prompts)])}
    assert got == ref, "replica placement changed the generated tokens"
    devs = jax.devices()
    for i, e in enumerate(rs.engines):
        leaf = jax.tree.leaves(e.params)[0]
        assert leaf.devices() == {devs[i]}, f"replica {i} params"
    assert rs.summary()["replica_devices"] == [[d.id] for d in devs[:4]]


def test_replicaset_least_loaded_dispatch():
    cfg = _cfg()
    rs = ReplicaSet.build(cfg, 2, max_batch=2, max_len=64,
                          prefill_buckets=(16, 32))
    prompts = _prompts(4, seed=13)
    picked = [rs.submit(Request(u, p, max_new_tokens=3))
              for u, p in enumerate(prompts)]
    # no prefix cache: dispatch alternates by load
    assert picked[0] is not picked[1]
    rs.run()
    assert sorted(rs.results()) == [0, 1, 2, 3]


# ------------------------------------------------- chip_smoke --chips 4 paths
def _chip_smoke():
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import chip_smoke
    return chip_smoke


def test_chip_smoke_tp_phase():
    """The four-chip smoke's tp=4 vs tp=1 comparison at reduced width,
    on a pool four times the tp=1 pool."""
    cs = _chip_smoke()
    cfg = reduced(get_config("h2o-danube-1.8b"))
    cfg = cfg.replace(n_kv_heads=4)
    plan = cs.Plan(requests=2, max_new=4, max_len=64)
    out = cs.tp_vs_single(cfg, plan, tp=4, pool_bytes_min=1 << 20)
    assert out["pool_bytes"] >= 1 << 20
    assert out["pool_bytes_per_shard"] * 4 == out["pool_bytes"]


def test_chip_smoke_dp_phase():
    cs = _chip_smoke()
    plan = cs.Plan(requests=4, max_new=4, max_len=64)
    out = cs.dp_vs_single(_cfg(), plan, dp=4)
    assert out["placed"] == [[d.id] for d in jax.devices()[:4]]
