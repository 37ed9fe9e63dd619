"""Share of the window's device-busy time in operations under none of the
program's named scopes (the first model's twelve, the ten by layer kind,
``ffn.dense``, ``ffn.post_norm`` and ``moe.shared``): how much the
per-scope metrics cannot see."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.unscoped_share(view)
