"""Device time of the FULL attention layers in one decode program —
projections and rotary, the write into the full group, the grouped core
over every cached position, the output projection: scopes ``attn.*.full``
inside a ``uccl.wire.decode`` span, median over the window's spans."""

from chipbench import scopes_mimo as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, sc.ATTENTION["full"])
