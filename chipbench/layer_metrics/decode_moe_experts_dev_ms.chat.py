"""Device time of the expert GEMMs (scope ``moe.experts``) in one decode
program: the operations that start inside a ``uccl.wire.decode`` span,
median over the window's spans."""

from chipbench import program_trace as pt


def read(view):
    return pt.scope_ms_in(view, pt.DECODE, pt.MOE_EXPERTS)
