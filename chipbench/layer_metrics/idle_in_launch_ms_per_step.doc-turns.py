"""Device idle time whose innermost program span is ``uccl.backend.launch``
(the call into the jitted programs until it returns), per engine step of
the window."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.idle_in_launch_ms_per_step(view)
