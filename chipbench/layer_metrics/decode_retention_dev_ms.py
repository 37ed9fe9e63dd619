"""Device time of the power-retention operators (projections and rotation,
the gate, the call's own scores, the state's read, update and write, the
normaliser and output projection) in one decode program: the family's group
``retention`` of scopes, over the operations that start inside a
``uccl.wire.decode`` span; median over the window's spans."""
from chipbench import scopes as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, "retention")
