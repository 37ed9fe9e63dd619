"""Host time of an engine step: the benchmark's span around
``engine.step()`` minus the device-busy time inside it, mean over the traced
steps. At depth 1 this is a far larger share of a step than in a 32-layer
deployment."""

from chipbench.runners.serve import NAME_STEP


def read(view):
    rows = view.tr.busy_per_span(view.ops(0), view.host_spans, NAME_STEP)
    if not rows:
        return None
    return sum(span - busy for busy, span, _ in rows) / len(rows) / 1e6
