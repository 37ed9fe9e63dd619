"""Device time of attention (scopes ``attn.*``) in one prefill program: the
operations that start inside a ``uccl.wire.prefill`` span, median over the
window's spans."""

from chipbench import program_trace as pt


def read(view):
    return pt.scope_ms_in(view, pt.PREFILL, pt.ATTENTION)
