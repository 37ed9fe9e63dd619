"""A decode program's share of its HBM roofline: the bytes the step must read
(the family's ``decode_step_bytes`` of the step's own ``uccl.wire.decode``
arguments — rows decoding, cached rows in use — and, where the family
takes it, the program's own count of the experts it reached: weights, the
experts reached, the cached rows in use) over the chip's HBM bandwidth, over
the device time of the operations inside that span taken as ONE union;
median over the window's decode spans."""
from chipbench import scopes as sc


def read(view):
    return sc.decode_roofline_share(view, "decode_step_bytes")
