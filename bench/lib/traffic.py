"""One generator for every traffic mix, driven by the mix's data file.

Lengths and inter-arrival gaps are stratified: a mix's distribution is
read at ``n`` evenly spaced quantiles, and the seed only permutes that
fixed set and draws the token ids.  Every seed therefore offers the same
work in another order, and runs of different seeds spread no wider than
runs of one seed.  ``n`` is the mix's ``strata`` (default 997); an open
loop that sets it to about rate x window seconds offers each window the
same set, since the window starts the seed's generator afresh.

A mix file holds:

* ``loop``: ``open`` (arrivals on the wall clock at ``rate`` requests/s,
  ``arrivals: poisson``) or ``closed`` (``clients`` that each send their
  next request when the last completes; ``"slots"`` means one per slot);
* ``prompt`` and ``output``: ``{"dist": "lognormal", "median", "sigma"}``
  or ``{"dist": "uniform"}``, each with ``min`` and ``max`` (clipped);
* ``greedy``: every request decodes greedily (the correctness check
  compares served tokens with the reference's logits);
* ``strata`` (optional): how many quantiles one set holds.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

STRATA = 997            # a prime, so the length and gap cycles never align


@dataclasses.dataclass(frozen=True)
class Item:
    uid: int
    prompt: np.ndarray      # int32 token ids
    max_new: int
    gap_s: float            # open loop: seconds after the previous arrival


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``dist`` at the quantiles (i + 1/2)/n, clipped, as whole numbers."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        vals = lo + u * (hi - lo + 1)
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(vals), lo, hi).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


class Generator:
    """Requests of one mix for one seed, in the order they are sent."""

    def __init__(self, mix: dict, seed: int, vocab: int, first: int = 0):
        """``first``: the first so many requests (a closed loop's opening
        round, one per client) are read at their own ``first`` evenly
        spaced quantiles in a fixed order, the longest prompt with the
        longest output first, so that round is the same for every seed
        (and set-up warms the same programs); the seed draws its token
        ids."""
        self.mix = mix
        self.strata = int(mix.get("strata", STRATA))
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.uid = 0
        self._prompts: list[int] = []
        self._outputs: list[int] = []
        self._gaps: list[float] = []
        if first:
            self._prompts = quantiles(mix["prompt"], first).tolist()
            self._outputs = quantiles(mix["output"], first).tolist()

    def _draw(self, pool: list, make) -> float:
        if not pool:
            pool.extend(self.rng.permutation(make()).tolist())
        return pool.pop()

    def next(self) -> Item:
        mix, n = self.mix, self.strata
        n_prompt = int(self._draw(self._prompts,
                                  lambda: quantiles(mix["prompt"], n)))
        max_new = int(self._draw(self._outputs,
                                 lambda: quantiles(mix["output"], n)))
        gap = 0.0
        if mix["loop"] == "open":
            gap = float(self._draw(self._gaps,
                                   lambda: exp_gaps(mix["rate"], n)))
        prompt = self.rng.integers(1, self.vocab, n_prompt, dtype=np.int32)
        self.uid += 1
        return Item(uid=self.uid, prompt=prompt, max_new=max_new, gap_s=gap)


def check_mix(mix: dict) -> None:
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"unknown loop {mix['loop']!r}")
    if mix["loop"] == "open" and not (mix["rate"] > 0 and math.isfinite(mix["rate"])):
        raise ValueError("an open loop needs a positive rate")
    if int(mix.get("strata", STRATA)) < 1:
        raise ValueError("a mix needs at least one stratum")
    if not mix.get("greedy", False):
        raise ValueError("the correctness check needs greedy requests")
