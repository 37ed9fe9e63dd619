"""Device idle time whose innermost program span is ``uccl.backend.fetch``
(the tail after the last operation ends until the tokens are read back on
the host), per engine step of the window."""

from chipbench import program_trace as pt


def read(view):
    return pt.idle_ms_per_step(view, pt.IDLE_FETCH)
