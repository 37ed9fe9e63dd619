"""Device time of the MoE layer around the expert GEMMs in one decode
program — router GEMM, routing, dispatch and combine (sort, gather, scatter
and the wire): scopes ``moe.router`` + ``moe.route`` + ``moe.dispatch`` +
``moe.combine`` inside a ``uccl.wire.decode`` span, median over the
window's spans."""

from chipbench import program_trace as pt


def read(view):
    return pt.scope_ms_in(view, pt.DECODE, pt.MOE_EXCHANGE)
