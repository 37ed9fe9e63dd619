"""Device time of the power-retention operators in one prefill program (a
chunk of every prefilling row: the chunk form): the family's group
``retention`` of scopes, over the operations that start inside a
``uccl.wire.prefill`` span; median over the window's spans."""
from chipbench import scopes as sc


def read(view):
    return sc.scope_ms_in(view, sc.PREFILL, "retention")
