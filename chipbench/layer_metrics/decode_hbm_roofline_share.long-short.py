"""A decode program's share of its HBM roofline: the bytes the step must read
(``flops_mimo.decode_step_bytes``: the bfloat16 attention, router, dense-
layer and head-slice weights, the held experts at least one decoding row
reaches — the expectation for the span's ``n`` rows of 8 draws over 256 —
the full layers' cached rows in use, ``min(length, 128)`` rows of each
ring) over the chip's HBM bandwidth, over the device time of the
operations inside the program's own ``uccl.wire.decode`` span; median."""

from chipbench import scopes_mimo as sc


def read(view):
    return sc.decode_hbm_roofline_share(view)
