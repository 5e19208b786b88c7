"""Benchmark substrate correctness: the pluggable-attention forward must
equal the production model forward, or every figure analog is meaningless."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the benchmarks package lives at the repo root
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import common  # noqa: E402
from repro.core.hdp import dense_attention_reference  # noqa: E402
from repro.models import registry  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    cfg = common.model_cfg("tiny")
    params, _ = registry.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                              cfg.vocab_size)
    return cfg, params, toks


def test_forward_with_attention_matches_model(tiny):
    cfg, params, toks = tiny
    ref, _ = registry.apply_train(cfg, params, {"tokens": toks})
    got = common.forward_with_attention(
        cfg, params, toks,
        lambda li, q, k, v: dense_attention_reference(q, k, v, causal=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_capture_layout(tiny):
    cfg, params, toks = tiny
    caps = common.capture_qkv(cfg, params, toks)
    assert len(caps) == cfg.n_layers
    B, S = toks.shape
    for c in caps:
        assert c["q"].shape == (B, cfg.n_heads, S, cfg.hd)
        assert bool(jnp.isfinite(c["q"]).all())


def test_agreement_is_one_for_dense(tiny):
    cfg, params, toks = tiny
    ag = common.agreement_with(
        cfg, params,
        lambda li, q, k, v: dense_attention_reference(q, k, v, causal=True),
        [np.asarray(toks)])
    assert ag == 1.0


def test_eval_batches_disjoint_from_training_stream():
    a = common.eval_batches(1, batch=4)[0]
    from repro.data.pipeline import DataConfig, make_source
    train = make_source(DataConfig(common.VOCAB, common.SEQ, 4,
                                   seed=3)).batch_at(0)
    assert not np.array_equal(a, train)
