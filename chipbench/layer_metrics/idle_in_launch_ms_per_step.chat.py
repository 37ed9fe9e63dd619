"""Device idle time whose innermost program span is ``uccl.backend.launch``
(the call into the jitted programs until it returns: Python, dispatch, and
the wait for the first operation to start), per engine step of the
window."""

from chipbench import program_trace as pt


def read(view):
    return pt.idle_ms_per_step(view, pt.IDLE_LAUNCH)
