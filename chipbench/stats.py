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


def range_spread(xs: Sequence[float]) -> Optional[float]:
    """The spread of a set of runs as the driver's check reckons it (ledger,
    PRs 28-29): the range of the runs over their median, the run farthest
    from the median left out. A bound holds where this stays under half of
    it, so the bounds in BENCHMARK.json are set from this one (until PR 30
    from the distance between the quartiles, which reads about half of it
    on six even runs and far more where one run is far off)."""
    if len(xs) < 2:
        return None
    med = statistics.median(xs)
    kept = sorted(xs, key=lambda x: abs(x - med))
    if len(kept) > 2:
        kept.pop()
    return (max(kept) - min(kept)) / med if med else None
