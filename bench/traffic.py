"""The one traffic generator: every mix is a data file it reads.

Lengths and arrival gaps are stratified: each block of requests takes the
same set of values (the distribution's quantiles at the block's midpoints)
and the seed only shuffles their order and draws the prompt tokens.  So
every seed offers the same work, and runs of different seeds differ by the
order of the work, not by its amount.

Mix keys:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when its last one finishes) or ``"open"`` (arrivals at fixed
  times, Poisson at ``rate_per_s``, whatever the server does).
* ``prompt_tokens`` / ``output_tokens``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``
  (integers, both ends included).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    """One request as generated: ``due`` is its arrival offset from the
    window's start in seconds (open loop), or None (closed loop: the client
    sends it when its previous request finishes)."""
    index: int
    client: int
    prompt: np.ndarray
    max_new_tokens: int
    due: float | None


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified values of a length distribution, in ascending
    order: its quantiles at (i + 0.5) / n, rounded and clipped."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = lo + u * (hi + 1 - lo) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """n stratified gaps of a Poisson process at ``rate`` per second."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def block_size(mix: dict) -> int:
    return int(mix["clients"]) if mix["loop"] == "closed" else 0


def generate(mix: dict, seed: int, vocab: int, seconds: float,
             n_closed: int = 0) -> list:
    """The requests of one run.

    Open loop: ``round(rate * seconds)`` arrivals whose gaps are one shuffled
    block of stratified exponential gaps, so every seed puts the same number
    of arrivals into the window.  Closed loop: ``n_closed`` requests (a
    multiple of ``clients`` is best), dealt out in blocks of ``clients``;
    request i belongs to client ``i % clients``."""
    rng = np.random.default_rng([int(seed), 7])
    if mix["loop"] == "open":
        n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
        dues = np.cumsum(rng.permutation(exp_gaps(float(mix["rate_per_s"]),
                                                  n)))
        block = n
    elif mix["loop"] == "closed":
        block = int(mix["clients"])
        n = max(int(n_closed), block)
        dues = None
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    pq = quantiles(mix["prompt_tokens"], block)
    oq = quantiles(mix["output_tokens"], block)
    items = []
    for start in range(0, n, block):
        m = min(block, n - start)
        plen = rng.permutation(pq)[:m]
        olen = rng.permutation(oq)[:m]
        for j in range(m):
            i = start + j
            items.append(Item(
                index=i, client=i % block if dues is None else i,
                prompt=rng.integers(1, vocab, int(plen[j]), dtype=np.int32),
                max_new_tokens=int(olen[j]),
                due=None if dues is None else float(dues[i])))
    return items
