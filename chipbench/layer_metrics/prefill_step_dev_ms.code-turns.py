"""Device-busy time of one ``[slots, chunk]`` prefill program (the
operations that start inside the benchmark's span around
``backend.prefill``), median."""

from chipbench.runners.serve import NAME_PREFILL
from chipbench.stats import percentile


def read(view):
    rows = view.tr.busy_per_span(view.ops(0), view.host_spans, NAME_PREFILL)
    busy = [b for b, _, _ in rows if b > 0]
    return percentile(busy, 50) / 1e6 if busy else None
