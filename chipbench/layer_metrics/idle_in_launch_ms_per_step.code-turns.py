"""Device idle time whose innermost program span is ``uccl.backend.launch``
(the call into the jitted programs until it returns: Python, dispatch, and
the wait for the first operation to start), per engine step of the
window."""

from chipbench import scopes_glm4 as sc


def read(view):
    return sc.idle_ms_per_step(view, sc.IDLE_LAUNCH)
