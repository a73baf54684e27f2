"""Open-loop request traffic: arrivals on a fixed schedule, lengths from named distributions.

A mix file (``bench/traffic/<mix>.json``, ``"generator": "open_loop"``)
gives the prompt and output length distributions; the cell gives the rate.
A window of ``seconds`` at ``rate`` requests per second holds
``n = round(rate * seconds)`` requests.  Every seed gets the same multiset
of prompt lengths, output lengths and inter-arrival gaps -- the
distributions' quantiles at ``(i + 0.5) / n`` (exponential gaps: Poisson
arrivals) -- so that seeds change the order and the tokens and not the
amount of work.  The seed orders each multiset on its own and draws the
prompt token ids uniformly from the vocabulary.  All requests decode greedily.

The order is a plain shuffle, or, where the mix gives ``"block": k``, a
shuffle stratified in blocks: the sorted multiset is cut into ``k`` strata
of neighbouring values, and each run of ``k`` consecutive requests (from
the first; ``order`` says how where ``k`` does not divide ``n``) takes one
value from each stratum, in a seeded order.  Every
stretch of the window then holds the whole distribution, so that which
long prompts, long answers and short gaps happen to coincide -- and with it
the queue a seed builds -- varies far less from seed to seed.

Length distributions (``{"dist": ..., ...}``):

- ``lognormal``: ``median * exp(sigma * z)``, clipped to [``min``, ``max``];
- ``loguniform``: log-uniform on [``min``, ``max``];
- ``uniform``: uniform on the integers [``min``, ``max``].
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, as ints."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "loguniform":
        v = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif dist == "uniform":
        v = np.floor(lo + u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.round(v), lo, hi).astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """Stratified exponential inter-arrival gaps (seconds) at ``rate``/s."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def order(rng: np.random.Generator, n: int, block: int) -> np.ndarray:
    """A seeded order of ``n`` ranks, cut into ``block`` strata of
    ``m = ceil(n / block)`` neighbouring ranks (the last strata may be short
    or empty), that comes in ``m`` consecutive groups: group ``t`` holds the
    ``t``-th of each stratum's shuffled ranks that has one, so a group holds
    ``block`` ranks, one from each stratum, where ``block`` divides ``n``.
    ``block >= n`` is a plain shuffle."""
    m = -(-n // block)
    strata = [rng.permutation(np.arange(j * m, min((j + 1) * m, n))) for j in range(block)]
    return np.concatenate([rng.permutation([s[t] for s in strata if t < s.size])
                           for t in range(m)]).astype(np.int64)


def generate(mix: dict, *, rate: float, seconds: float, seed: int, vocab: int) -> list[dict]:
    """Requests ``{"rid", "due", "prompt", "max_new"}`` sorted by ``due``
    (seconds from the window's start)."""
    n = max(1, int(round(rate * seconds)))
    block = int(mix.get("block", n))
    rng = np.random.default_rng(int(seed))
    plen = lengths(mix["prompt"], n)[order(rng, n, block)]
    olen = lengths(mix["output"], n)[order(rng, n, block)]
    due = np.cumsum(gaps(rate, n)[order(rng, n, block)])
    return [
        {"rid": i, "due": float(due[i]), "max_new": int(olen[i]),
         "prompt": rng.integers(0, vocab, int(plen[i]), dtype=np.int64).astype(np.int32)}
        for i in range(n)
    ]
