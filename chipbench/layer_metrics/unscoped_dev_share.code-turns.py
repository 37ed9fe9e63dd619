"""Share of the window's device-busy time in operations under none of the
program's named scopes (the first model's twelve and the four this model
adds): how much the per-scope metrics cannot see."""

from chipbench import scopes_glm4 as sc


def read(view):
    return sc.unscoped_share_in(view)
