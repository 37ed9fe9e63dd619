"""Request traffic from a mix file and a seed: the ``open_loop`` and
``backlog`` arrival kinds.

Every seed gets the SAME multiset of prompt lengths, output lengths and
inter-arrival gaps — the distribution's own quantiles at the midpoints of
``n`` equal-probability strata — in a drawn order of each. Without
``order_seed`` the order is drawn from the run's seed: two seeds offer the
same work in the same window and differ in who arrives beside whom. Since
PR 28 that alone changes the work (three prompts prefilling together run
the pool-wide program at five times the price of one or two), so a cell's
mix pins the order with ``order_seed``: every seed then offers the same
lengths at the same times, one fixed schedule as a load generator replays
it, and the seed draws the tokens (and the weights). A run-to-run
difference is then the system's, not the draw's.

A mix file (``chipbench/traffic/<mix>.json``) holds::

    {"generator": "open_loop",          # or "backlog"
     "rate_rps": 1.2,                   # open_loop: mean arrivals per second
     "requests": 2000,                  # backlog: all due at t = 0
     "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                    "min": 32, "max": 2048},
     "output_len": {"dist": "uniform", "min": 8, "max": 32},
     "order_seed": 576721147,           # optional: the order of lengths and
                                        # gaps is drawn from this, not --seed
     "drain_s": 15,                     # cap on finishing what holds a slot
     "attempted": "due"}                # or "admitted" (a backlog)

The program under test receives only what this returns.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Traffic:
    due_s: np.ndarray  # [n] seconds after the window opens, ascending
    prompts: List[np.ndarray]  # n 1-D int32 token arrays
    output_lens: np.ndarray  # [n] tokens each request asks for


def _strata(n: int) -> np.ndarray:
    """Midpoints of n equal-probability strata of (0, 1)."""
    return (np.arange(n) + 0.5) / n


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """The n stratum-midpoint quantiles of a length distribution, clipped to
    [min, max] and rounded to whole tokens (ascending)."""
    u = _strata(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", math.inf)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def n_requests(mix: dict, seconds: float) -> int:
    if mix["generator"] == "backlog":
        return int(mix["requests"])
    if mix["generator"] == "open_loop":
        return max(1, int(round(mix["rate_rps"] * seconds)))
    raise ValueError(f"not a request generator: {mix['generator']!r}")


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> Traffic:
    """The window's requests. ``open_loop``: round(rate x seconds) requests
    whose gaps are the exponential distribution's stratum quantiles in a
    drawn order, scaled so the last falls due just inside the window (a
    Poisson stream's gaps without its sampling noise in their sum).
    ``backlog``: ``requests`` requests all due at 0. With ``order_seed`` in
    the mix the three orders are what ``generate(mix, order_seed, ...)``
    drew before the key existed, and the tokens come from the seed."""
    n = n_requests(mix, seconds)
    pinned = "order_seed" in mix
    rng = np.random.default_rng(mix["order_seed"] if pinned else seed)
    plens = rng.permutation(quantile_lengths(mix["prompt_len"], n))
    olens = rng.permutation(quantile_lengths(mix["output_len"], n))
    if mix["generator"] == "backlog":
        due = np.zeros(n)
    else:
        # n - 1 gaps between n arrivals: the first request is due when the
        # window opens, the last half a mean gap before it closes; the gaps'
        # sum is the same for every seed
        gaps = rng.permutation(-np.log1p(-_strata(max(n - 1, 1))))[:n - 1]
        span = seconds * (n - 0.5) / n
        due = np.concatenate([[0.0], np.cumsum(gaps)]) * (
            span / max(float(np.sum(gaps)), 1e-9))
    if pinned:
        rng = np.random.default_rng([seed, 0x70CE25])  # the tokens' own stream
    prompts = [rng.integers(0, vocab, int(l)).astype(np.int32) for l in plens]
    return Traffic(due_s=due, prompts=prompts, output_lens=olens)
