"""What every traffic generator shares: requests, lengths, gaps, token ids.

A mix file (``traffic/<mix>.json``) names its ``kind``, the generator
``generators/<kind>.py`` that reads it, and holds its parameters, such as
``prompt`` / ``output`` length distributions (``lognormal`` with
``median``, ``sigma``, ``min``, ``max``).  Every seed gets the same set of
lengths and gaps: the quantiles ``(i + 0.5) / n`` of their distributions;
a generator decides their order.  Token ids are uniform over
``[1, vocab)`` from the seed.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List, Optional

import numpy as np

_SEED_MASK = (1 << 64) - 1


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt: List[int]
    max_new: int
    due: Optional[float] = None     # seconds after the window opens


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & _SEED_MASK, *stream])


def strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's stratified quantiles, sorted."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf(u) for u in strata(n)])
    x = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def gamma_gaps(rate: float, cv: float, n: int, seconds: float) -> np.ndarray:
    """``n`` stratified gamma gaps of mean ``1/rate``, scaled to sum to
    ``seconds`` so that the last arrival falls inside the span."""
    from scipy.stats import gamma
    k = 1.0 / (cv * cv)
    g = gamma.ppf(strata(n), k, scale=1.0 / (rate * k))
    return g * (seconds / g.sum())


def tokens(seed: int, index: int, n: int, vocab: int) -> List[int]:
    return rng(seed, 2, index).integers(1, vocab, size=int(n)).tolist()
