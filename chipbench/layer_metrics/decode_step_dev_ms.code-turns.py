"""Device-busy time of one decode-step program (the operations that start
inside the benchmark's span around ``backend.decode``), median."""

from chipbench.runners.serve import NAME_DECODE
from chipbench.stats import percentile


def read(view):
    rows = view.tr.busy_per_span(view.ops(0), view.host_spans, NAME_DECODE)
    busy = [b for b, _, _ in rows if b > 0]
    return percentile(busy, 50) / 1e6 if busy else None
