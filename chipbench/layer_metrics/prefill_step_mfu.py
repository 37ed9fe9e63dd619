"""A whole prefill program's share of the chip's bfloat16 peak: the FLOPs
its real tokens need (the family's ``prefill_flops`` of the
``uccl.wire.prefill`` span's arguments: 2 x the matrix parameters a token,
the operator's own terms, the head for the one position a row that is read)
over the peak, over the device time of the operations inside that span taken
as ONE union; median over the window's prefill spans. Padding rows and a
last chunk's padded tail are computed and do not count."""
from chipbench.prefill_shares import prefill_peak_share


def read(view):
    return prefill_peak_share(view, "prefill_flops", None)
