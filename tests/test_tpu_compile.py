"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test compiles for a described (not attached)
``v5e:2x2`` topology, so the TPU compiler refuses here what it would
refuse on the chip — a block shape off the (8, 128) tiling, a dynamic
lane slice, too much VMEM. Shapes are qwen2-1.5b's (hd 128, 2 kv heads x
6 query heads, 128-token pages) and granite-8b's 8 kv heads for the
tensor-parallel case. Each test asserts the Mosaic kernel
(``tpu_custom_call``) is in the compiled program, and the engine's decode
step is checked to hand the page pool to the kernel in place.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU library.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.hdp_block_attn import hdp_block_sparse_attention
from repro.kernels.hdp_paged_decode import hdp_paged_fum_decode
from repro.kernels.hdp_scout import hdp_scout

F32, I32 = jnp.float32, jnp.int32
# qwen2-1.5b decode at batch 8, max_len 2048: 16 pages per slot
B, N, G, HD, PS, MK = 8, 2, 6, 128, 128, 16
P = 1 + B * MK
S = 1024                                   # prefill kernels' sequence
H = N * G


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(autouse=True)
def _serving_defaults(monkeypatch):
    """These tests compile the default main path: the feature settings a
    CI leg exports (backend, policy, speculation, ...) do not apply."""
    for k in list(os.environ):
        if k.startswith("REPRO_"):
            monkeypatch.delenv(k)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("sq", [1, 4])
def test_paged_decode_compiles(one_chip, pool_dtype, sq):
    """Gather-free FUM decode over fp32 / bf16 / int8 pools, plain decode
    (Sq=1) and the speculative verify shape (Sq=4)."""
    dt = jnp.dtype(pool_dtype)
    args = [_sds((B, N, G, sq, HD), F32, one_chip),
            _sds((P, N, PS, HD), dt, one_chip),
            _sds((P, N, PS, HD), dt, one_chip),
            _sds((B, MK), I32, one_chip), _sds((B, MK), I32, one_chip),
            _sds((B,), I32, one_chip),
            _sds((B, MK, N, G, sq), I32, one_chip),
            _sds((B,), I32, one_chip)]
    if dt == jnp.int8:
        args += [_sds((P, N), F32, one_chip), _sds((P, N), F32, one_chip)]

        def fn(*a):
            return hdp_paged_fum_decode(*a[:8], k_scale=a[8], v_scale=a[9])
    else:
        fn = hdp_paged_fum_decode
    _assert_kernel(fn, *args)


def test_scout_compiles(one_chip):
    x = _sds((1, H, S, HD), F32, one_chip)
    _assert_kernel(lambda a, b: hdp_scout(a, b, rho_b=0.5), x, x)


def test_block_sparse_compiles(one_chip):
    nq = S // 128
    x = _sds((1, H, S, HD), F32, one_chip)
    _assert_kernel(hdp_block_sparse_attention, x, x, x,
                   _sds((1, H, nq, nq), I32, one_chip),
                   _sds((1, H, nq), I32, one_chip),
                   _sds((1, H), I32, one_chip))


def test_flash_compiles(one_chip):
    x = _sds((1, H, S, HD), jnp.bfloat16, one_chip)
    _assert_kernel(flash_attention, x, x, x)


def test_tp_paged_attention_compiles(topo, monkeypatch):
    """Head-sharded paged decode at tp=4 over the described chips: the
    registry resolves the Pallas kernel inside shard_map, as on a TPU."""
    from repro.attention.registry import resolve_backend
    from repro.distribution.tp import pool_pspec, tp_paged_attention
    from repro.launch.mesh import make_serving_mesh
    from repro.models.attention import build_attn_call

    # steer the TPU-only choices (backend priority, compiled kernels)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("granite-8b")
    n, g, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    mesh = make_serving_mesh(tp=4, devices=topo.devices)
    call = build_attn_call(cfg, mode="decode", paged=True, per_slot=True)
    assert resolve_backend(call).name == "pallas_paged_decode"
    rep = NamedSharding(mesh, PartitionSpec())
    pool = {"k_pages": ((P, n, PS, hd), jnp.int8),
            "v_pages": ((P, n, PS, hd), jnp.int8),
            "k_scale": ((P, n), F32), "v_scale": ((P, n), F32)}
    cache = {k: _sds(shp, dt, NamedSharding(
        mesh, pool_pspec(k, per_layer=True))) for k, (shp, dt) in pool.items()}
    q = _sds((B, n, g, 1, hd), F32, rep)
    table = _sds((B, MK), I32, rep)
    q_pos = _sds((B, 1, 1, 1), I32, rep)
    k_pos = _sds((B, 1, 1, MK * PS), I32, rep)

    def fn(q, cache, table, q_pos, k_pos):
        return tp_paged_attention(q, call, None, q_pos=q_pos, k_pos=k_pos,
                                  cache=cache, page_table=table,
                                  mesh=mesh)[0]

    compiled = jax.jit(fn).lower(q, cache, table, q_pos, k_pos).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text


def _producers(text):
    """Instruction name -> (shape, opcode, operand names, rest of line)
    of an HLO dump."""
    out = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+?)(?:\{[^}]*\})? "
                     r"([\w-]+)\((.*)", line)
        if m:
            name, shape, op, rest = m.groups()
            out[name] = (shape, op, re.findall(r"%([\w.-]+)", rest), rest)
    return out


def test_decode_step_hands_pool_to_kernel_in_place(topo, monkeypatch):
    """The engine's decode step at qwen2-1.5b widths (two layers): the
    paged kernel reads the layer-stacked int8 pool the step carries — no
    copy, relayout or per-layer slice of the pool feeds it, so the only
    pool bytes moved are the scout stream and the surviving pages the
    kernel DMAs."""
    from repro.models import registry
    from repro.serving import Engine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("qwen2-1.5b").replace(n_layers=2)
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(
        lambda: registry.init_params(cfg, jax.random.PRNGKey(0))[0])
    params = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one), params)
    b = 2
    eng = Engine(cfg, params=params, max_batch=b, max_len=4 * PS,
                 prefill_buckets=(PS,), tp=1)
    assert eng.resolved_backend("decode") == "pallas_paged_decode"
    pool = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one),
                        eng.pages.cache)
    stacked = pool["k_pages"].shape
    # the stacked pool, or one layer's slice of it (with or without its
    # unit layer dim)
    pool_shapes = {"s8[" + ",".join(map(str, s)) + "]"
                   for s in (stacked, stacked[1:], (1,) + stacked[1:])}

    def v(shape, dt=I32):
        return _sds(shape, dt, one)

    text = eng._decode_jit.lower(
        1, 0, params, v((b, 1)), pool, v((b, eng.pages.pages_per_slot)),
        v((b,)), v((b,)), v((b,), jnp.bool_), v((b,)), v((b,)),
        v((b,), jnp.bool_)).compile().as_text()
    prod = _producers(text)
    calls = [n for n, (_, op, _, rest) in prod.items()
             if op == "custom-call" and "tpu_custom_call" in rest]
    assert calls, "no paged decode kernel in the decode step"
    for call in calls:
        pools = [o for o in prod[call][2]
                 if prod.get(o, ("",))[0] in pool_shapes]
        assert len(pools) == 2, f"{call}: K/V pool operands not found"
        for o in pools:
            # the pool arrives as the loop carries it, updated in place by
            # the decode write — never as a copy or a fusion's output
            assert prod[o][1] in ("get-tuple-element", "parameter",
                                  "dynamic-update-slice"), \
                f"{call}: pool operand {o} comes from {prod[o][1]}"
    assert not [n for n, (shape, op, _, _) in prod.items()
                if shape in pool_shapes and op == "copy"], \
        "the decode step copies the whole pool"
