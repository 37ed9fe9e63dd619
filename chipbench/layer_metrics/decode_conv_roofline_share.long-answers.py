"""The conv operators' share of their HBM roofline in a decode program: the
seven layers' bfloat16 matrices (16.8 M parameters each) and three ring
rows a decoding row and layer (``flops_lfm2.conv_decode_bytes``) over the
chip's HBM bandwidth, over the device time under ``conv.*`` in the
``uccl.wire.decode`` span; median."""

from chipbench import scopes_lfm2 as sc


def read(view):
    return sc.decode_conv_roofline_share(view)
