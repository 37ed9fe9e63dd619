"""Device time of the WINDOW attention layers in one decode program —
projections, QK-norm and rotary, the write into the ring, the grouped core
over the ring's 4,224 rows, the gate, the output projection and its closing
norm: scopes ``attn.*.window`` inside a ``uccl.wire.decode`` span, median
over the window's spans."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, sc.ATTENTION["window"])
