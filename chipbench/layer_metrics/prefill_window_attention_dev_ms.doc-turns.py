"""Device time of the WINDOW attention layers in one prefill program
(``[1 | 2 | slots, chunk]``): scopes ``attn.*.window`` inside a
``uccl.wire.prefill`` span, median over the window's spans."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.scope_ms_in(view, sc.PREFILL, sc.ATTENTION["window"])
