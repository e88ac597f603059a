"""Arrival schedules drawn from the run's seed.

Every seed gets the same work in another order, so that runs with different
seeds differ no more than two runs of one seed:

* an open loop at ``rate_per_s`` for ``seconds`` sends ``round(rate *
  seconds)`` invocations. Its gaps are the quantiles of the exponential
  distribution (the gaps of a Poisson process), scaled to fill the window,
  and the seed shuffles them. Each function's count is its Zipf share of the
  total, rounded by largest remainder, and the seed shuffles which arrival
  goes to which function;
* a closed loop has ``callers`` clients, each sending its next invocation
  when the last returns; the seed only picks the invocations' inputs.

Each invocation carries a 32-bit seed of its own, derived from the run's
seed, from which the program draws its tokens.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Sequence

import numpy as np


def derive_seed(seed: int, *salt: int) -> int:
    """A 32-bit seed from any non-negative ``seed`` and salt. JAX's
    ``PRNGKey`` keeps only the low 32 bits of a larger seed, so every key the
    benchmark makes goes through here."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return int(np.random.SeedSequence([seed, *salt]).generate_state(1)[0])


def zipf_shares(n: int, s: float) -> List[float]:
    """Popularity of ranks 1..n under Zipf's law with exponent ``s``."""
    w = [1.0 / (k ** s) for k in range(1, n + 1)]
    total = sum(w)
    return [x / total for x in w]


def split_counts(total: int, shares: Sequence[float]) -> List[int]:
    """``total`` split by ``shares``, rounded by largest remainder."""
    raw = [total * s for s in shares]
    counts = [math.floor(x) for x in raw]
    order = sorted(range(len(raw)), key=lambda i: (counts[i] - raw[i], i))
    for i in order[:total - sum(counts)]:
        counts[i] += 1
    return counts


@dataclass(frozen=True)
class Arrival:
    t: float        # seconds after the window opens
    function: int   # index of the function (0 = most popular)
    seed: int       # the invocation's input seed


def open_loop(seed: int, *, seconds: float, rate_per_s: float,
              functions: int, zipf_s: float) -> List[Arrival]:
    """Open-loop Poisson arrivals over Zipf-popular functions."""
    n = round(rate_per_s * seconds)
    if n < 1:
        raise ValueError(f"{rate_per_s}/s over {seconds}s sends nothing")
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng(derive_seed(seed, 0))
    rng.shuffle(gaps)
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    labels = np.repeat(np.arange(functions),
                       split_counts(n, zipf_shares(functions, zipf_s)))
    rng.shuffle(labels)
    return [Arrival(float(t), int(f), derive_seed(seed, 1, i))
            for i, (t, f) in enumerate(zip(starts, labels))]


def closed_loop_seed(seed: int, caller: int, k: int) -> int:
    """Input seed of caller ``caller``'s ``k``-th invocation."""
    return derive_seed(seed, 2, caller, k)
