"""Device time of the HELD experts' GEMMs (scope ``moe.experts``) in one
decode program: the operations that start inside a ``uccl.wire.decode``
span, median over the window's spans."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, sc.MOE_EXPERTS)
