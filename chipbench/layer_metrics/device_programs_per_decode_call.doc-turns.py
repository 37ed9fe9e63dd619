"""Median count of the program runs on the chip's ``XLA Modules`` line that
start inside a decode call's ``uccl.wire.decode`` span
(``chipbench/step_timeline.py``): the step's own program and every implicit
one dispatched beside it."""

from chipbench.step_timeline import device_programs_per_decode_call as read  # noqa: F401
