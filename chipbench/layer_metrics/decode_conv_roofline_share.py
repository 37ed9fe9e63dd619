"""The short-convolution operators' share of its HBM roofline in a
decode program: the bytes it must move at least once (the family's
``conv_decode_bytes`` of the ``uccl.wire.decode`` span's arguments: the rows in
use, not the rows the program reads) over the chip's HBM bandwidth, over the
device time under the family's group ``conv`` in that span;
median over the window's decode spans."""
from chipbench import scopes as sc


def read(view):
    return sc.decode_roofline_share(view, "conv_decode_bytes", "conv")
