"""Suite-wide test options.

``REPRO_TEST_MESH_TP=<n>`` serves every engine the suite builds at
tensor-parallel degree ``n`` wherever that engine can honour it (a paged
layout, a kv-head count ``n`` divides, enough devices — give the CPU
devices through ``XLA_FLAGS=--xla_force_host_platform_device_count``), so
one CI leg runs the whole serving suite through the sharded page pool.
Engines built with an explicit ``tp``/``mesh``, and engines that cannot
shard (a dense layout, a kv-head count ``n`` does not divide), are built
as the test wrote them. The product's own ``REPRO_MESH_TP`` refuses a
degree it cannot honour; this option is the test-only way to apply one
degree across a suite of mixed engines.
"""
from __future__ import annotations

import os

import pytest

TEST_MESH_TP_ENV = "REPRO_TEST_MESH_TP"


@pytest.fixture(autouse=True)
def _suite_mesh_tp(monkeypatch):
    tp = int(os.environ.get(TEST_MESH_TP_ENV) or 1)
    if tp > 1:
        from repro.serving.engine import (MESH_TP_ENV, Engine,
                                          TensorParallelUnsupported)

        init = Engine.__init__

        def sharded_init(self, cfg, *args, **kw):
            # a test that sets the product's REPRO_MESH_TP tests that
            # setting: leave its engines to it
            if (kw.get("tp") is None and kw.get("mesh") is None
                    and not os.environ.get(MESH_TP_ENV)):
                try:
                    return init(self, cfg, *args, **{**kw, "tp": tp})
                except TensorParallelUnsupported:
                    pass
            return init(self, cfg, *args, **kw)

        monkeypatch.setattr(Engine, "__init__", sharded_init)
    yield
