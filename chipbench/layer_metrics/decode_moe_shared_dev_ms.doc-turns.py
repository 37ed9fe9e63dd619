"""Device time of the shared expert every token passes beside the held
share (scope ``moe.shared``), all expert layers, in one decode program:
the operations that start inside a ``uccl.wire.decode`` span, median over
the window's spans."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, sc.MOE_SHARED)
