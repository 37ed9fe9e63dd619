"""Device idle time whose innermost program span is ``uccl.backend.stage``
(host arrays to device arrays before a backend call), per engine step of
the window."""

from chipbench import program_trace as pt


def read(view):
    return pt.idle_ms_per_step(view, pt.IDLE_STAGE)
