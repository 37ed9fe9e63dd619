"""Device time of the MoE layer around the expert GEMMs in one prefill
program: scopes ``moe.router`` + ``moe.route`` + ``moe.dispatch`` +
``moe.combine`` inside a ``uccl.wire.prefill`` span, median over the
window's spans."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.scope_ms_in(view, sc.PREFILL, sc.MOE_EXCHANGE)
