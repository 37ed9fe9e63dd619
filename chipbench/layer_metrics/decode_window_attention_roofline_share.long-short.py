"""The window layers' attention core's share of its HBM roofline in a decode
program: ``min(length, 128)`` rows of each decoding slot's ring x 2,560
float32 numbers x window layers (``flops_mimo.window_cache_bytes``) over
the chip's HBM bandwidth, over the device time under
``attn.kv_write.window`` + ``attn.core.window`` in the ``uccl.wire.decode``
span; median. The program reads the ring's 256 rows: at most half."""

from chipbench import scopes_mimo as sc


def read(view):
    return sc.decode_attention_roofline_share(view, "window")
