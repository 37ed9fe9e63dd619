"""Device time of the routed experts' GEMMs (the experts the chip holds) in one
prefill program: the family's group ``moe_experts`` of scopes, over the
operations that start inside a ``uccl.wire.prefill`` span; median over the
window's spans."""
from chipbench import scopes as sc


def read(view):
    return sc.scope_ms_in(view, sc.PREFILL, "moe_experts")
