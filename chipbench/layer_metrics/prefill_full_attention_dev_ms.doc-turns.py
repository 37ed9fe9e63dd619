"""Device time of the FULL attention layer in one prefill program
(``[1 | 2 | slots, chunk]``): scopes ``attn.*.full`` inside a
``uccl.wire.prefill`` span, median over the window's spans."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.scope_ms_in(view, sc.PREFILL, sc.ATTENTION["full"])
