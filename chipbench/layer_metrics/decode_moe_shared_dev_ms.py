"""Device time of the shared expert every token passes beside the routed
ones, all expert layers, in one decode program: the family's group
``moe_shared`` of scopes, over the operations that start inside a
``uccl.wire.decode`` span; median over the window's spans."""
from chipbench import scopes as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, "moe_shared")
