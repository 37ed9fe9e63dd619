"""Median, over the window's decode calls, of the time from the last
operation of the step's own program on the chip to ``uccl.backend.fetch``
closing (``chipbench/step_timeline.py``), in ms: the trivial programs after
the step's and every device-to-host read."""

from chipbench.step_timeline import decode_completion_latency_ms as read  # noqa: F401
