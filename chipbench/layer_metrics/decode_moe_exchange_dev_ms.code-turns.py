"""Device time of the MoE layer around the expert GEMMs in one decode
program — router GEMM and sigmoid gate, routing, dispatch and combine:
scopes ``moe.router`` + ``moe.route`` + ``moe.dispatch`` + ``moe.combine``
inside a ``uccl.wire.decode`` span, median over the window's spans."""

from chipbench import scopes_glm4 as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, sc.MOE_EXCHANGE)
