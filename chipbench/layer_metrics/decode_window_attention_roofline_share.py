"""The window layers' attention over the rings' share of its HBM roofline in a
decode program: the bytes it must move at least once (the family's
``window_cache_bytes`` of the ``uccl.wire.decode`` span's arguments: the
rows in use, not the rows the program reads) over the chip's HBM bandwidth,
over the device time under the family's group ``cache_read.window`` in that
span; median over the window's decode spans."""
from chipbench import scopes as sc


def read(view):
    return sc.decode_roofline_share(view, "window_cache_bytes",
                                    "cache_read.window")
