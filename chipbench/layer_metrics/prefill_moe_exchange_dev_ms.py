"""Device time of the MoE layer around the expert GEMMs (router GEMM and gate,
routing onto the queues, dispatch and combine) in one prefill program: the
family's group ``moe_exchange`` of scopes, over the operations that start
inside a ``uccl.wire.prefill`` span; median over the window's spans."""
from chipbench import scopes as sc


def read(view):
    return sc.scope_ms_in(view, sc.PREFILL, "moe_exchange")
