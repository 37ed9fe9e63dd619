"""Median, over the window's decode calls, of the time from
``uccl.backend.launch`` opening to the first operation of the step's own
program on the chip (``chipbench/step_timeline.py``), in ms: Python, the
trivial program dispatched before the step's, the runtime's enqueue."""

from chipbench.step_timeline import decode_dispatch_latency_ms as read  # noqa: F401
