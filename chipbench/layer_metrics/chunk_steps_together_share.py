"""Of the window's chunk steps (``chunk_step_host_ms``: the
``uccl.engine.step`` spans that hold a prefill call and a decode call), the
share, in %, whose span says its two calls were launched before either was
read (``calls`` = ``together``; the engine's
``serving_chunk_step_calls_total`` counts the same word). 0 on a program
whose steps say nothing of their calls; None without spans."""

from chipbench.layer_metrics.chunk_step_host_ms import chunk_steps


def read(view):
    steps = chunk_steps(view)
    if not steps:
        return None
    together = sum(1 for sp in steps
                   if len(sp) > 3 and sp[3].get("calls") == "together")
    return 100.0 * together / len(steps)
