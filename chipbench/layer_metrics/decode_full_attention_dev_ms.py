"""Device time of the FULL attention layers (projections, the write into the
full group, the core over every cached position, the output projection and
what the family has beside them: a gate, norms) in one decode program: the
family's group ``full_attention`` of scopes, over the operations that start
inside a ``uccl.wire.decode`` span; median over the window's spans."""
from chipbench import scopes as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, "full_attention")
