"""The while-aware HLO cost parser, validated against ground truth.

The parser exists because ``cost_analysis()`` counts scan bodies once;
these tests prove the parser's totals equal (a) hand-computed flops and
(b) XLA's own cost_analysis on the *unrolled* program, and that SPMD
collective bytes match analytic expectations.
"""
from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.roofline import analysis, hlo_cost


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


class TestTripCounts:
    def test_scan_flops_equal_unrolled(self):
        def scanned(x, ws):
            def body(c, w):
                return c @ w, ()
            return jax.lax.scan(body, x, ws)[0]

        def unrolled(x, ws):
            def body(c, w):
                return c @ w, ()
            return jax.lax.scan(body, x, ws, unroll=10)[0]

        x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        ws = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
        ps = hlo_cost.module_cost(_compile(scanned, x, ws).as_text())
        cu = _compile(unrolled, x, ws)
        pu = hlo_cost.module_cost(cu.as_text())
        ca = cu.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        expect = 10 * 2 * 256 ** 3
        assert ps.flops == pytest.approx(expect, rel=0.01)
        assert pu.flops == pytest.approx(expect, rel=0.01)
        assert ps.flops == pytest.approx(float(ca["flops"]), rel=0.01)

    def test_nested_scan_multiplies(self):
        def nested(x, ws):
            def outer(c, _):
                def inner(ci, w):
                    return jnp.tanh(ci @ w), ()
                return jax.lax.scan(inner, c, ws)[0], ()
            return jax.lax.scan(outer, x, None, length=4)[0]

        x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        ws = jax.ShapeDtypeStruct((6, 128, 128), jnp.float32)
        c = hlo_cost.module_cost(_compile(nested, x, ws).as_text())
        expect = 4 * 6 * 2 * 128 ** 3
        assert c.flops == pytest.approx(expect, rel=0.02)

    def test_scanned_weights_read_once_per_iter(self):
        """Bytes: the [L,...] weight stack streams once per scan, not L
        times (the dynamic-slice override)."""
        L, D = 8, 256

        def scanned(x, ws):
            def body(c, w):
                return c @ w, ()
            return jax.lax.scan(body, x, ws)[0]

        x = jax.ShapeDtypeStruct((D, D), jnp.float32)
        ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
        c = hlo_cost.module_cost(_compile(scanned, x, ws).as_text())
        stack = L * D * D * 4
        slice_bytes = D * D * 4
        # the per-iteration weight slice is charged at slice volume
        # (read+write of the sliced copy = 2x per iter), NOT the full
        # stack: a broken override would charge ~stack per iteration.
        ds = sum(v for k, v in c.bytes_by_label.items()
                 if "dynamic_slice" in k)
        assert ds <= 2.5 * slice_bytes * L, (ds, c.bytes_by_label)
        # and total traffic (dot reads/writes + loop-carry copies) stays
        # below the stack-per-iteration blowup (~2x the correct total)
        assert c.bytes < stack * L


class TestCollectives:
    def test_spmd_allreduce_bytes(self):
        """Needs >1 host device -> separate process with XLA_FLAGS."""
        code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_host_mesh
from repro.roofline import hlo_cost
mesh = make_host_mesh(model=8)
x = jax.ShapeDtypeStruct((256, 1024), jnp.float32,
                         sharding=NamedSharding(mesh, P(None, "model")))
w = jax.ShapeDtypeStruct((1024, 2048), jnp.float32,
                         sharding=NamedSharding(mesh, P("model", None)))
c = jax.jit(lambda a, b: a @ b,
            out_shardings=NamedSharding(mesh, P())).lower(x, w).compile()
pc = hlo_cost.module_cost(c.as_text())
assert abs(pc.flops - 2*256*1024*2048/8) / (2*256*1024*2048/8) < 0.01, pc.flops
assert pc.coll_by_kind.get("all-reduce", 0) == 256*2048*4, pc.coll_by_kind
print("OK")
"""
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True,
                             env={**__import__("os").environ,
                                  "PYTHONPATH": "src"},
                             cwd=Path(__file__).resolve().parents[1],
                             timeout=300)
        assert "OK" in out.stdout, out.stderr[-2000:]


class TestAnalysis:
    def test_analyze_shape(self):
        def f(a, b):
            return jnp.tanh(a @ b)

        a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
        compiled = _compile(f, a, a)
        r = analysis.analyze(compiled, model_flops_per_device=2 * 512 ** 3)
        assert r.bottleneck in ("compute", "memory", "collective")
        assert r.flops == pytest.approx(2 * 512 ** 3, rel=0.01)
        assert 0.9 < r.useful_ratio < 1.1
        assert r.top_flops and r.top_bytes
        d = r.as_dict()
        assert {"compute_t", "memory_t", "collective_t"} <= set(d)

    def test_model_flops_kinds(self):
        from repro.configs import SHAPES, get_config
        cfg = get_config("qwen2-1.5b")
        tr = analysis.model_flops(cfg, SHAPES["train_4k"], 256)
        pf = analysis.model_flops(cfg, SHAPES["prefill_32k"], 256)
        de = analysis.model_flops(cfg, SHAPES["decode_32k"], 256)
        assert tr > pf > de > 0
