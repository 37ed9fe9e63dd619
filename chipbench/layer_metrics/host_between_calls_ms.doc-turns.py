"""Median, over the decode calls of consecutive steps (``step`` n and n + 1,
neither with a ``uccl.wire.prefill``), of the time from this call's
``uccl.backend.fetch`` closing to the next one's ``uccl.backend.launch``
opening (``chipbench/step_timeline.py``), in ms: retire, the harness's loop,
admit, the next call's arrays and stage. ``None`` on a program whose spans
carry no ``step``."""

from chipbench.step_timeline import host_between_calls_ms as read  # noqa: F401
