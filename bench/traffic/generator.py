"""Open-loop chat sessions, generated from a seed and a mix's parameters.

A mix file (``bench/traffic/<mix>.json``) gives:

* ``live_sessions``: sessions alive at any time; a session that ends is
  replaced at once by a fresh one, so the count stays fixed;
* ``zipf_s``: popularity exponent over the live sessions' ranks;
* ``prompt_lens``: first-turn prompt lengths, used in equal shares;
* ``new_tokens``: [low, high], output tokens per turn, uniform;
* ``turns_mean``: mean turns of a session: in each block (below) a
  ``1 / turns_mean`` share of the arrivals open a fresh session in the
  slot they pick, so a session's turns are about geometric with that mean;
  a session also ends before its history would pass ``cache_cap`` (taken
  from the configuration);
* ``knee_req_s``: the highest rate the cell sustains, found by
  ``bench/sweep.py``;
* ``phases``: a cycle of ``{"seconds", "x_knee"}``: the offered rate is
  ``x_knee * knee_req_s`` during each phase, and the cycle repeats;
* ``block``: arrivals per stratification block (below);
* ``drain``: read by ``bench/run.py``, not here: true, the window closes
  once every request due in it is served; false (a mix offered above
  capacity), it closes at its length and leaves the backlog unserved.

Every seed gets the same amount of work in another order.  Arrivals come
in blocks of ``block``: within a block the gaps are the ``block`` quantiles
of an exponential distribution (a Poisson stream's gaps) in an order drawn
from the seed, and a block always spans the same unit time.  Output
lengths, session picks (inverse-CDF at stratified points) and the
arrivals that open a fresh session are stratified the same way, and fresh
sessions take the prompt lengths in turn.  Times are generated for a
unit-rate stream and mapped through the phases' cumulative rate.

A session's requests all carry its first prompt: the serve loop continues a
session it still holds from its last token, and replays the prompt where
it lost it.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> Dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


@dataclass
class Turn:
    due_s: float            # offset from the window's start (warm-up: 0)
    session: str
    prompt: np.ndarray      # int32 token ids, the session's first prompt
    new_tokens: int


@dataclass
class Schedule:
    warmup: List[Turn]      # first turn of every initial session, then one
    #                         more turn of the first (it finds its KV moved);
    #                         the shortest output, as set-up serves no user
    window: List[Turn]      # due in [0, seconds), in due order


def _stratified(rng: np.random.Generator, n: int) -> np.ndarray:
    """n points in (0, 1), one in each of n equal strata, shuffled."""
    return rng.permutation((np.arange(n) + rng.random(n)) / n)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _rate_map(mix: Dict, unit_times: np.ndarray) -> np.ndarray:
    """Times at which a stream with the mix's phased rate reaches the given
    cumulative counts (unit_times of a rate-1 stream)."""
    knee = float(mix["knee_req_s"])
    phases = [(float(p["seconds"]), float(p["x_knee"]) * knee)
              for p in mix["phases"]]
    per_cycle = sum(s * r for s, r in phases)
    cycle_s = sum(s for s, _ in phases)
    out = np.empty_like(unit_times)
    for i, u in enumerate(unit_times):
        cycles, rest = divmod(u, per_cycle)
        t = cycles * cycle_s
        for s, r in phases:
            if rest <= s * r:
                t += rest / r
                break
            rest -= s * r
            t += s
        out[i] = t
    return out


class _Sessions:
    """Live session slots: who is in each, its prompt and history."""

    def __init__(self, mix: Dict, vocab: int, cap: int,
                 rng: np.random.Generator):
        self.rng = rng
        self.vocab = vocab
        self.cap = cap
        self.lens = list(mix["prompt_lens"])
        self.lo, self.hi = (int(x) for x in mix["new_tokens"])
        self.count = 0
        n = int(mix["live_sessions"])
        self.slots = [self._fresh() for _ in range(n)]

    def _fresh(self) -> Dict:
        sid = f"c{self.count}"
        length = self.lens[self.count % len(self.lens)]
        self.count += 1
        prompt = self.rng.integers(0, self.vocab, length, dtype=np.int32)
        return {"sid": sid, "prompt": prompt, "history": len(prompt)}

    def turn(self, slot: int, new_tokens: int, due_s: float,
             fresh: bool = False) -> Turn:
        s = self.slots[slot]
        if fresh or s["history"] + new_tokens >= self.cap - 1:
            s = self.slots[slot] = self._fresh()
        s["history"] += new_tokens
        return Turn(due_s, s["sid"], s["prompt"], new_tokens)


def schedule(mix: Dict, seed: int, seconds: float, vocab: int,
             cap: int) -> Schedule:
    """The warm-up turns and every request due in the window."""
    rng = np.random.default_rng(int(seed))
    sessions = _Sessions(mix, vocab, cap, rng)
    n_live = len(sessions.slots)
    ranks = np.arange(1, n_live + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(mix["zipf_s"]))
    cdf /= cdf[-1]
    order = rng.permutation(n_live)         # slot of each popularity rank
    warmup = [sessions.turn(i, sessions.lo, 0.0) for i in range(n_live)]
    warmup.append(sessions.turn(0, sessions.lo, 0.0))

    block = int(mix["block"])
    gaps = -np.log1p(-_quantiles(block))
    gaps *= block / gaps.sum()              # a block spans ``block`` units
    lengths = sessions.lo + np.floor(
        _quantiles(block) * (sessions.hi - sessions.lo + 1)).astype(int)
    opens = np.arange(block) < round(block / float(mix["turns_mean"]))
    window: List[Turn] = []
    unit = 0.0
    while True:
        unit_t = unit + np.cumsum(rng.permutation(gaps))
        unit = unit_t[-1]
        times = _rate_map(mix, unit_t)
        picks = np.searchsorted(cdf, _stratified(rng, block), side="right")
        for t, rank, n, fresh in zip(times, picks, rng.permutation(lengths),
                                     rng.permutation(opens)):
            if t >= seconds:
                return Schedule(warmup, window)
            window.append(sessions.turn(int(order[min(rank, n_live - 1)]),
                                        int(n), float(t), bool(fresh)))

