"""Checkpointing (atomic, resume, elastic) and fault-tolerance units."""
from __future__ import annotations

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.training import checkpoint as ckpt
from repro.training import fault


def _state(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": jnp.asarray(rng.normal(size=(4, 8)), jnp.float32),
                   "b": jnp.asarray(rng.normal(size=(8,)), jnp.float32)},
        "opt": {"step": jnp.asarray(3, jnp.int32),
                "m": {"w": jnp.zeros((4, 8)), "b": jnp.ones((8,))}},
    }


class TestCheckpoint:
    def test_save_load_roundtrip(self, tmp_path):
        s = _state()
        ckpt.save_checkpoint(str(tmp_path), 10, s, meta={"loss": 1.5})
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), s)
        out, step, meta = ckpt.load_checkpoint(str(tmp_path), like)
        assert step == 10 and meta["loss"] == 1.5
        for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_latest_and_retention(self, tmp_path):
        for step in (1, 2, 3, 4):
            ckpt.save_checkpoint(str(tmp_path), step, _state(step), keep=2)
        assert ckpt.latest_step(str(tmp_path)) == 4
        kept = sorted(d for d in os.listdir(tmp_path)
                      if d.startswith("step_"))
        assert len(kept) == 2

    def test_structure_mismatch_rejected(self, tmp_path):
        ckpt.save_checkpoint(str(tmp_path), 1, _state())
        bad = {"params": {"w": jax.ShapeDtypeStruct((4, 8), jnp.float32)}}
        with pytest.raises(ValueError):
            ckpt.load_checkpoint(str(tmp_path), bad)

    def test_shape_mismatch_rejected(self, tmp_path):
        s = _state()
        ckpt.save_checkpoint(str(tmp_path), 1, s)
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), s)
        like["params"]["w"] = jax.ShapeDtypeStruct((5, 8), jnp.float32)
        with pytest.raises(ValueError):
            ckpt.load_checkpoint(str(tmp_path), like)

    def test_elastic_reshard_onto_shardings(self, tmp_path):
        """Leaves stored as full logical arrays restore under any sharding
        — here a 1-device mesh stands in for a resized cluster."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        s = _state()
        ckpt.save_checkpoint(str(tmp_path), 2, s)
        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))
        sh = jax.tree.map(lambda x: NamedSharding(mesh, P()), s)
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), s)
        out, step, _ = ckpt.load_checkpoint(str(tmp_path), like,
                                            shardings=sh)
        assert step == 2
        w = jax.tree.leaves(out)[0]
        assert w.sharding.mesh.shape == {"data": 1, "model": 1}

    def test_manager_restore_or_init(self, tmp_path):
        mgr = ckpt.CheckpointManager(str(tmp_path), interval=2, keep=2)
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), _state())
        st0, step0, _ = mgr.restore_or(like, _state)
        assert step0 == 0
        assert mgr.maybe_save(1, st0) is None      # not on interval
        assert mgr.maybe_save(2, st0) is not None  # on interval
        _, step1, _ = mgr.restore_or(like, _state)
        assert step1 == 2

    def test_atomic_no_partial_dirs(self, tmp_path):
        ckpt.save_checkpoint(str(tmp_path), 5, _state())
        entries = os.listdir(tmp_path)
        assert not [e for e in entries if ".tmp" in e]
        man = json.load(open(tmp_path / "step_00000005" / "manifest.json"))
        assert man["n_leaves"] == len(jax.tree.leaves(_state()))


class TestFault:
    def test_step_timer_flags_straggler(self, monkeypatch):
        # a scripted clock: step times are exact however loaded the host
        clock = [0.0]
        monkeypatch.setattr(fault.time, "perf_counter", lambda: clock[0])
        t = fault.StepTimer(window=20, threshold=2.0, warmup=0)
        for i in range(10):
            t.start()
            clock[0] += 0.002
            t.stop(i)
        t.start()
        clock[0] += 0.05  # 25x median
        t.stop(10)
        assert len(t.events) == 1
        assert t.events[0].slowdown > 2.0
        assert t.summary()["stragglers"] == 1

    def test_watchdog_fires_and_beats(self):
        fired = threading.Event()
        with fault.Watchdog(0.15, fired.set, poll_s=0.02) as wd:
            for _ in range(5):   # heartbeats keep it quiet
                time.sleep(0.05)
                wd.beat()
            assert not wd.fired
            time.sleep(0.3)      # silence -> fire
        assert fired.is_set() and wd.fired

    def test_retry_recovers_with_hook(self):
        calls = {"n": 0, "restored": 0}

        def flaky(x):
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("collective timeout")
            return x + 1

        out = fault.retry(flaky, 41, retries=3, backoff_s=0.01,
                          on_retry=lambda a, e: calls.__setitem__(
                              "restored", calls["restored"] + 1))
        assert out == 42 and calls["restored"] == 2

    def test_retry_exhausts(self):
        def dead(_):
            raise RuntimeError("down")
        with pytest.raises(RuntimeError):
            fault.retry(dead, 0, retries=1, backoff_s=0.01)

    def test_elastic_mesh_shape(self):
        assert fault.elastic_mesh_shape(256, 16) == (16, 16)
        assert fault.elastic_mesh_shape(240, 16) == (15, 16)   # lost a host
        assert fault.elastic_mesh_shape(512, 16, pod=2) == (2, 16, 16)
        with pytest.raises(ValueError):
            fault.elastic_mesh_shape(8, 16)
