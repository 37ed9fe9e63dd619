"""Device-busy time of one prefill program (the operations that start
inside the benchmark's span around ``backend.prefill``), median over the
window's: since PR 28 the program over the prefilling slots' rows,
``[1 | 2, chunk]``, and the pool's ``[slots, chunk]`` with three or more."""

from chipbench.runners.serve import NAME_PREFILL
from chipbench.stats import percentile


def read(view):
    rows = view.tr.busy_per_span(view.ops(0), view.host_spans, NAME_PREFILL)
    busy = [b for b, _, _ in rows if b > 0]
    return percentile(busy, 50) / 1e6 if busy else None
