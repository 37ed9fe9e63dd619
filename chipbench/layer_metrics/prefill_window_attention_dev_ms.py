"""Device time of the WINDOW attention layers (projections, the write into the
ring, the core over the ring's rows, the output projection and what the
family has beside them: a sink, a gate, norms) in one prefill program: the
family's group ``window_attention`` of scopes, over the operations that
start inside a ``uccl.wire.prefill`` span; median over the window's spans."""
from chipbench import scopes as sc


def read(view):
    return sc.scope_ms_in(view, sc.PREFILL, "window_attention")
