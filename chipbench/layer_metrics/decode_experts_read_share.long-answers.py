"""Experts whose weights a decode program's expert GEMMs read over the 64
held x 8 expert layers (the program's own count, on its
``uccl.ep.experts`` span inside ``uccl.wire.decode``), in %; median over
the window's decode spans. With a dozen rows decoding, each drawing 4 of
64, a step reaches about half; ``None`` on a program that reports no
count."""

from chipbench import scopes_lfm2 as sc


def read(view):
    return sc.decode_experts_read_share(view)
