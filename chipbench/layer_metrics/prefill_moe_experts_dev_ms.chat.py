"""Device time of the expert GEMMs (scope ``moe.experts``) in one prefill
program (``[1 | 2 | slots, chunk]`` since PR 28): the operations that start
inside a ``uccl.wire.prefill`` span, median over the window's spans."""

from chipbench import program_trace as pt


def read(view):
    return pt.scope_ms_in(view, pt.PREFILL, pt.MOE_EXPERTS)
