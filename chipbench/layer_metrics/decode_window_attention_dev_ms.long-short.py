"""Device time of the WINDOW attention layers in one decode program —
projections and rotary, the write into the ring, the grouped core with its
sink over the ring's rows, the output projection: scopes
``attn.*.window`` inside a ``uccl.wire.decode`` span, median over the
window's spans."""

from chipbench import scopes_mimo as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, sc.ATTENTION["window"])
