"""The one traffic generator: reads a mix file and makes the requests.

A mix (``bench/traffic/<name>.json``) is data only:

* ``loop``: ``"closed"`` — one client per engine slot, each sending its
  next request the moment its last one finished (agents, long-document
  reasoning); ``"open"`` — independent users arriving on a schedule in
  wall-clock seconds, whether or not earlier requests have finished.
* ``prompt`` / ``output``: length distributions, ``{"dist": "uniform",
  "lo", "hi"}`` or ``{"dist": "lognormal", "median", "sigma", "lo",
  "hi"}`` (clipped to ``[lo, hi]``).
* ``rate_per_s`` (open loop): mean arrival rate.
* ``start`` (closed loop): ``"mid_decode"`` starts every client's first
  request as a session caught mid-decode — its context is the prompt plus
  the part of its output already generated, its budget the rest — with
  the remaining budgets spread as in steady state, so completions are
  staggered from the first second on and the window opens in steady
  state.

Every seed gets the same work in another order. Lengths come in blocks —
a closed loop's requests in blocks of one per client, an open loop's as
the window's arrivals and then the drain's — and each block holds the
quantiles ``(i + 0.5) / n`` of the length distribution. A closed loop's
blocks are permuted by the seed. An open loop's window holds exactly
``rate * seconds`` arrivals, placed uniformly at random in it (a Poisson
process conditioned on its count); its arrival gaps and (prompt, output)
pairs are one schedule for every seed, which the seed reorders only
within runs of ``REORDER`` (4) consecutive requests, so
the load at each moment of the window, and the requests cut off by its
end, are nearly the same from seed to seed. A closed loop's first wave
is caught at the quantiles of the steady state's remaining budgets (the
residual life of a renewal process with the mix's output lengths),
matched in order to its outputs, so the window's completions fall at the
same times for every seed. Token ids are uniform over the vocabulary;
request ``k``'s ids come from ``(seed, k)`` alone, so they do not depend
on the order in which a closed loop's clients ask for them.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()
#: the open loop's one schedule of arrivals and lengths, which each run's
#: seed only reorders locally, within runs of REORDER requests
SCHEDULE_SEED = 20240717
REORDER = 4


@dataclass
class Req:
    """One request: ``index`` in the mix's sequence, ``due`` (open loop:
    seconds after the window opens; closed loop: None), the prompt's
    token ids and the generation budget."""

    index: int
    prompt: np.ndarray
    max_new: int
    due: Optional[float] = None


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``spec``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    elif spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(p)) for p in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def local_order(n: int, k: int, rng) -> np.ndarray:
    """A permutation of ``range(n)`` that moves items only within runs of
    ``k`` consecutive positions."""
    return np.concatenate([start + rng.permutation(min(k, n - start))
                           for start in range(0, n, k)] or [np.zeros(0, int)])


def _tokens(seed: int, index: int, n: int, vocab: int,
            stream: int = 1) -> np.ndarray:
    rng = np.random.default_rng([int(seed), int(index), stream])
    return rng.integers(1, vocab, size=n, dtype=np.int64).astype(np.int32)


class Traffic:
    """Requests of one mix for one seed."""

    def __init__(self, mix: dict, *, slots: int, vocab: int, max_len: int,
                 seed: int, seconds: float, drain_seconds: float = 0.0):
        self.mix, self.vocab, self.seed = mix, vocab, int(seed)
        self.closed = mix["loop"] == "closed"
        if mix["loop"] not in ("closed", "open"):
            raise ValueError(f"unknown loop {mix['loop']!r}")
        rng = np.random.default_rng([self.seed, 0])
        self.in_window = 0
        if self.closed:
            self.clients = slots
            n = -(-int(mix["requests"]) // self.clients) * self.clients
        else:
            rate = float(mix["rate_per_s"])
            n_win = int(round(rate * seconds))
            n_drain = int(math.ceil(rate * drain_seconds))
            n = n_win + n_drain
            self.in_window = n_win
        self.n = n
        blocks = ([self.clients] * -(-n // self.clients) if self.closed
                  else [b for b in (self.in_window, n - self.in_window) if b])
        if self.closed:
            self.prompt_len = self._blocks(mix["prompt"], blocks, rng)[:n]
            self.output_len = self._blocks(mix["output"], blocks, rng)[:n]
        else:
            self._open_schedule(mix, blocks, seconds, drain_seconds, rng)
        over = self.prompt_len + self.output_len - max_len
        if (over > 0).any():
            raise ValueError(f"mix exceeds max_len {max_len} by {over.max()}")
        if self.closed:
            self.remaining = self._residual_budgets(mix["output"], rng)
        self._next = 0

    @staticmethod
    def _blocks(spec: dict, blocks: List[int], rng) -> np.ndarray:
        return np.concatenate([rng.permutation(quantiles(spec, b))
                               for b in blocks])

    def _open_schedule(self, mix: dict, blocks: List[int], seconds: float,
                       drain_seconds: float, rng) -> None:
        """Due times and lengths of an open loop: one schedule, drawn from
        ``SCHEDULE_SEED`` whatever the run's seed, that the seed reorders
        within runs of ``REORDER`` consecutive requests."""
        fixed = np.random.default_rng(SCHEDULE_SEED)
        k = REORDER
        due, order, first = [], [], 0
        for (a, b), m in zip([(0.0, seconds), (seconds, seconds + drain_seconds)],
                             blocks):
            gaps = np.diff(np.sort(fixed.uniform(a, b, m)), prepend=a)
            due.append(a + np.cumsum(gaps[local_order(m, k, rng)]))
            order.append(first + local_order(m, k, rng))
            first += m
        self.due = np.concatenate(due)
        order = np.concatenate(order)
        self.prompt_len = self._blocks(mix["prompt"], blocks, fixed)[order]
        self.output_len = self._blocks(mix["output"], blocks, fixed)[order]

    def _residual_budgets(self, spec: dict, rng) -> np.ndarray:
        """Remaining budgets of the first wave, one per client, matched to
        the first block's outputs: the quantiles of the steady state's
        residual budget, P(R > r) proportional to sum(max(O - r, 0)) over
        the output lengths O, paired in sorted order (so R <= O)."""
        outs = quantiles(spec, 1024).astype(np.float64)
        grid = np.arange(1, int(outs.max()) + 1, dtype=np.float64)
        tail = np.maximum(outs[None, :] - grid[:, None], 0).sum(1)
        cdf = 1.0 - tail / outs.sum()
        u = (np.arange(self.clients) + 0.5) / self.clients
        r = grid[np.searchsorted(cdf, u)].astype(np.int64)
        first = self.output_len[:self.clients]
        order = np.argsort(first, kind="stable")
        out = np.empty(self.clients, np.int64)
        out[order] = np.minimum(np.sort(r), first[order])
        return np.maximum(out, 1)

    def request(self, k: int) -> Req:
        """Request ``k`` of the sequence (open loop: with its due time). A
        closed loop cycles through its ``requests`` lengths."""
        if k >= self.n and not self.closed:
            raise IndexError(f"mix holds {self.n} requests")
        p, o = int(self.prompt_len[k % self.n]), int(self.output_len[k % self.n])
        due = None if self.closed else float(self.due[k])
        return Req(k, _tokens(self.seed, k, p, self.vocab), o, due)

    # ---------------------------------------------------------- closed loop
    def first_wave(self) -> List[Req]:
        """Each client's first request. With ``start: mid_decode`` it is a
        session caught mid-decode: the context is the prompt plus a share
        ``u`` of the output (as prompt tokens), the budget the rest."""
        out = []
        for i in range(self.clients):
            r = self.request(i)
            if self.mix.get("start") == "mid_decode":
                done = r.max_new - int(self.remaining[i])
                extra = _tokens(self.seed, i, done, self.vocab, stream=2)
                r = Req(i, np.concatenate([r.prompt, extra]), r.max_new - done)
            out.append(r)
        self._next = self.clients
        return out

    def next_request(self) -> Req:
        """The next request of the closed loop's shared sequence."""
        r = self.request(self._next)
        self._next += 1
        return r
