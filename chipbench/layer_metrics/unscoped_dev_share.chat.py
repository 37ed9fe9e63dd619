"""Share of the window's device-busy time in operations under none of the
program's named scopes: how much the per-scope metrics cannot see (what the
compiler inserted without metadata, and the few operations between the
blocks)."""

from chipbench import program_trace as pt


def read(view):
    return pt.unscoped_share_in(view)
