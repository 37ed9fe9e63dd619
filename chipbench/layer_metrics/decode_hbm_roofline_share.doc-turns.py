"""A decode program's share of its HBM roofline: the bytes the step must read
(``flops_afmoe.decode_step_bytes``: the bfloat16 attention, gate, router,
shared-expert, dense-layer and head-slice weights, the held experts at
least one decoding row reaches — the expectation for the span's ``n`` rows
of 4 draws over 256 — the full layer's cached rows in use, ``min(length,
4096)`` rows of each ring, 2,048 float32 numbers a row) over the chip's HBM
bandwidth, over the device time of the operations inside the program's own
``uccl.wire.decode`` span; median."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.decode_hbm_roofline_share(view)
