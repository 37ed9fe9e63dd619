"""Host time of a CHUNK step, the step that sets ``itl_p90_ms`` where a
prompt's chunk is prefilled beside the rows that decode: over the window's
``uccl.engine.step`` spans that hold both a ``uccl.wire.prefill`` and a
``uccl.wire.decode`` span, the span's length less the device-busy time of
the operations that start inside it, median, in ms. The step's span holds
both programs whole however its two calls are laid out inside it (in turn,
or both launched before either is read), so the reading is cut at no
boundary that moves with the layout. A step in which no operation starts
is left out (a trace that overflowed the profiler's buffer keeps the host's
spans and loses the device's events). None on a program without spans."""

from bisect import bisect_left

from chipbench import program_trace as pt
from chipbench import trace_reduce as tr
from chipbench.stats import percentile


def chunk_steps(view):
    """The window's ``uccl.engine.step`` spans that hold (by containment)
    both a prefill call and a decode call, by start; None without the
    program's trace."""
    loaded = pt._loaded(view)
    if loaded is None:
        return None
    starts = {name: [sp[1] for sp in loaded.spans if sp[0] == name]
              for name in (pt.PREFILL, pt.DECODE)}

    def holds(name, lo, hi):
        at = bisect_left(starts[name], lo)
        return at < len(starts[name]) and starts[name][at] < hi

    return [sp for sp in pt.spans_in(loaded.spans, pt.STEP, *view.window)
            if holds(pt.PREFILL, sp[1], sp[1] + sp[2])
            and holds(pt.DECODE, sp[1], sp[1] + sp[2])]


def read(view):
    steps = chunk_steps(view)
    if not steps:
        return None
    ops = pt._window_ops(view.record["trace_path"], *view.window)
    host = [span - busy for busy, span, _ in
            tr.busy_per_span(ops, steps, pt.STEP) if busy > 0]
    return percentile(host, 50) / 1e6 if host else None
