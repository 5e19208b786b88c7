"""A run whose timed path is broken underneath comes out not correct:
one case per fault a served cell can have."""
import jax.numpy as jnp
import pytest

from repro.serving.engine import Engine


def altered_token(monkeypatch):
    real = Engine._decode_step

    def step(self, *a, **kw):
        nxt, bad, cache, stats = real(self, *a, **kw)
        return (nxt + 1) % self.cfg.vocab_size, bad, cache, stats
    monkeypatch.setattr(Engine, "_decode_step", step)


def state_unchanged(monkeypatch):
    real = Engine._decode_step

    def step(self, params, token, cache, *a, **kw):
        nxt, bad, _, stats = real(self, params, token, cache, *a, **kw)
        return nxt, bad, cache, stats          # K/V of the step never kept
    monkeypatch.setattr(Engine, "_decode_step", step)


def half_context_left_out(monkeypatch):
    real = Engine._decode_step

    def step(self, params, token, cache, pos, table, *a, **kw):
        if table is not None:                   # attend half the pages
            cols = jnp.arange(table.shape[1])[None]
            table = jnp.where(cols < (pos[:, None] // 128 + 1) // 2, table, 0) \
                + jnp.where(cols == pos[:, None] // 128, table, 0)
        return real(self, params, token, cache, pos, table, *a, **kw)
    monkeypatch.setattr(Engine, "_decode_step", step)


@pytest.mark.parametrize("fault", [altered_token, state_unchanged,
                                   half_context_left_out])
def test_broken_timed_path_is_not_correct(fault, monkeypatch, tiny_run):
    fault(monkeypatch)
    out = tiny_run(seed=11)
    assert not out["correct"], out["check"]
    assert out["check"]["mean_gap"]["value"] > out["check"]["mean_gap"]["limit"]
