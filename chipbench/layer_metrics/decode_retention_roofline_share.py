"""The power-retention operators' share of their HBM roofline in a decode
program: the bytes they must move at least once (the family's
``retention_decode_bytes`` of the ``uccl.wire.decode`` span's ``n``: the
layers' q, k, v, g, o matrices and the state of the rows that DECODE read
and written, not that of every slot the program passes over) over the
chip's HBM bandwidth, over the device time under the family's group
``retention`` in that span; median over the window's decode spans."""
from chipbench import scopes as sc


def read(view):
    return sc.decode_roofline_share(view, "retention_decode_bytes",
                                    "retention")
