"""Device time of the FULL attention layer in one decode program —
projections and QK-norm (no rotation), the write into the full group, the
grouped core over every cached position, the gate, the output projection
and its closing norm: scopes ``attn.*.full`` inside a ``uccl.wire.decode``
span, median over the window's spans."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.scope_ms_in(view, sc.DECODE, sc.ATTENTION["full"])
