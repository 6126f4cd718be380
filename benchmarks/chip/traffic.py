"""The one traffic generator: reads a mix's parameters, draws requests.

Lengths are lognormal (median, sigma) clipped to [min, max].  Every seed
gets the same multiset of prompt lengths, answer lengths and, for an
open loop, inter-arrival gaps: they are stratified quantiles of the
distributions, and the seed only permutes them and draws the token ids.
So two seeds differ in order and content, never in the amount of work.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

_N = NormalDist()


@dataclasses.dataclass
class Planned:
    """One request as the client will send it."""
    idx: int
    prompt: np.ndarray      # (plen,) int32 token ids
    max_new: int
    due: float = 0.0        # seconds after the window opens (open loop)


def _quantiles(n: int, spec: Dict[str, float]) -> np.ndarray:
    """``n`` stratified draws of a clipped lognormal, as integers."""
    u = (np.arange(n) + 0.5) / n
    z = np.array([_N.inv_cdf(x) for x in u])
    v = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(v, spec["min"], spec["max"]).astype(np.int64)


def _lengths(mix, n: int, rng: np.random.Generator):
    return (rng.permutation(_quantiles(n, mix["prompt"])),
            rng.permutation(_quantiles(n, mix["output"])))


def open_loop(mix, rate: float, seconds: float, vocab: int,
              seed: int) -> List[Planned]:
    """Poisson arrivals at ``rate`` requests/s, all due inside
    [0, seconds): ``round(rate * seconds)`` requests whose gaps are the
    exponential's stratified quantiles in a seeded order, scaled so the
    last is due just before the window closes."""
    rng = np.random.default_rng([seed, 1])
    n = max(1, round(rate * seconds))
    plen, nout = _lengths(mix, n, rng)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    due = np.cumsum(gaps) * (seconds * (n - 0.5) / n) / gaps.sum()
    return [Planned(i, rng.integers(0, vocab, plen[i], dtype=np.int32),
                    int(nout[i]), float(due[i])) for i in range(n)]


def closed_loop(mix, vocab: int, seed: int) -> Iterator[Planned]:
    """An endless stream of requests for the clients to take in turn:
    each block of one request per client repeats the same multiset of
    lengths in a fresh seeded order.  A window takes a few such blocks
    whole, so every seed gives it nearly the same prompt and answer
    tokens."""
    rng = np.random.default_rng([seed, 2])
    block = int(mix["clients"])
    idx = 0
    while True:
        plen, nout = _lengths(mix, block, rng)
        for i in range(block):
            yield Planned(idx, rng.integers(0, vocab, plen[i],
                                            dtype=np.int32), int(nout[i]))
            idx += 1

