"""A decode program's share of its HBM roofline: the bytes the step must read
(``flops_lfm2.decode_step_bytes``: the bfloat16 conv, attention, router,
dense-layer and embedding-as-head weights, the experts the step's decoding
rows REACHED by the program's own count, the attention layers' cached rows
in use at 1,024 float32 numbers a row, three ring rows of 2,048 a decoding
row and conv layer) over the chip's HBM bandwidth, over the device time of
the operations inside the program's own ``uccl.wire.decode`` span; median."""

from chipbench import scopes_lfm2 as sc


def read(view):
    return sc.decode_hbm_roofline_share(view)
