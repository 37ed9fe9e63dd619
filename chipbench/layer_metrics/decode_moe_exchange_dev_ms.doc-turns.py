"""Device time of the MoE layer around the expert GEMMs in one decode
program — router GEMM over all the routed experts and sigmoid gate, routing
onto the held queues, dispatch and combine: scopes ``moe.router`` +
``moe.route`` + ``moe.dispatch`` + ``moe.combine`` inside a
``uccl.wire.decode`` span, median over the window's spans."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, sc.MOE_EXCHANGE)
