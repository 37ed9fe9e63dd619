"""Host time of an engine step: the benchmark's span around
``engine.step()`` minus the device-busy time inside it, mean over the steps
that start inside the measured window. (The operations are cut to the
window, so a step of the drain would count whole as host time; the trace
runs on through the drain.) At a reduced depth this is a far larger share of
a step than in a deployment."""
from chipbench.runners.serve import NAME_STEP


def read(view):
    if view.window is None:
        return None
    t0, t1 = view.window
    steps = [sp for sp in view.host_spans
             if sp[0] == NAME_STEP and t0 <= sp[1] < t1]
    rows = view.tr.busy_per_span(view.ops(0), steps, NAME_STEP)
    if not rows:
        return None
    return sum(span - busy for busy, span, _ in rows) / len(rows) / 1e6
