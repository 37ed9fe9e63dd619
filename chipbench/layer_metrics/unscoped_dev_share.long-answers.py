"""Share of the window's device-busy time in operations under none of the
program's named scopes (the first model's twelve, ``attn.*.full``,
``conv.*`` and ``ffn.dense``): how much the per-scope metrics cannot see."""

from chipbench import scopes_lfm2 as sc


def read(view):
    return sc.unscoped_share(view)
