"""The power-retention operators' share of their roofline in a prefill
program: the least time the chip could take for the span's real tokens —
the larger of the family's ``retention_prefill_flops`` over the bfloat16
peak and ``retention_prefill_bytes`` over the HBM bandwidth — over the
device time under the family's group ``retention`` in that
``uccl.wire.prefill`` span; median over the window's prefill spans."""
from chipbench.prefill_shares import prefill_peak_share


def read(view):
    return prefill_peak_share(view, "retention_prefill_flops",
                              "retention_prefill_bytes", "retention")
