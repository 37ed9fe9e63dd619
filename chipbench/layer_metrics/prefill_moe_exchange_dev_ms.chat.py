"""Device time of the MoE layer around the expert GEMMs in one prefill
program: scopes ``moe.router`` + ``moe.route`` + ``moe.dispatch`` +
``moe.combine`` inside a ``uccl.wire.prefill`` span, median over the
window's spans."""

from chipbench import program_trace as pt


def read(view):
    return pt.scope_ms_in(view, pt.PREFILL, pt.MOE_EXCHANGE)
