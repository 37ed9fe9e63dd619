"""Device time of the short-convolution operators (the gates' projection, the
ring write and read, the taps, the output projection) in one prefill
program: the family's group ``conv`` of scopes, over the operations that
start inside a ``uccl.wire.prefill`` span; median over the window's spans."""
from chipbench import scopes as sc


def read(view):
    return sc.scope_ms_in(view, sc.PREFILL, "conv")
