"""Order statistics shared by the runners, the metric readers and the
measurement scripts: one definition, so a percentile in a result line and
one in PERF.md are the same arithmetic."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolation percentile (numpy's default); None when empty."""
    if not len(xs):
        return None
    s = sorted(xs)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    if lo + 1 >= len(s):
        return float(s[-1])
    frac = pos - lo
    return float(s[lo] * (1.0 - frac) + s[lo + 1] * frac)


def spread(xs: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(xs, n=4)`` — the spread the bounds in
    BENCHMARK.json are set from."""
    if len(xs) < 2:
        return None
    q1, med, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / med if med else None
