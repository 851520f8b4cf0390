"""Seeded draws the generators share.

A run is a fixed amount of work drawn from the seed: lengths and choices
are drawn by jittered stratification (one draw from each of n equal slices
of the distribution, in a seeded order), so every seed sends the same
spread of lengths and only their order, pairing and tokens differ.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def stratified_uniform(rng: np.random.Generator, n: int) -> np.ndarray:
    """n numbers in [0, 1), one in each slice [i/n, (i+1)/n), shuffled."""
    return (rng.permutation(n) + rng.random(n)) / n


def lognormal_lengths(rng, n: int, median: float, sigma: float,
                      lo: int, hi: int) -> list:
    """Whole lengths from a lognormal with this median and sigma (of the
    logarithm), clipped to [lo, hi]."""
    normal = NormalDist()
    u = np.clip(stratified_uniform(rng, n), 1e-9, 1 - 1e-9)
    return [int(min(hi, max(lo, round(median * math.exp(
        sigma * normal.inv_cdf(float(x))))))) for x in u]


def uniform_lengths(rng, n: int, lo: int, hi: int) -> list:
    """Whole lengths uniform on [lo, hi]."""
    return [lo + int(x * (hi - lo + 1)) for x in stratified_uniform(rng, n)]


def zipf_choices(rng, n: int, k: int, s: float) -> list:
    """n choices among k items with popularity 1/rank^s."""
    weights = np.array([1.0 / (r + 1) ** s for r in range(k)])
    edges = np.cumsum(weights / weights.sum())
    return [int(min(np.searchsorted(edges, x, side="right"), k - 1))
            for x in stratified_uniform(rng, n)]


def tokens(rng, n: int, vocab: int) -> list:
    return rng.integers(0, vocab, n).tolist()
