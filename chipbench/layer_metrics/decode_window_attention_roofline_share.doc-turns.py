"""The window layers' attention over the rings' share of its HBM roofline in
a decode program: ``min(length, 4096)`` rows of each decoding slot
(``window_rows`` of the ``uccl.wire.decode`` span) x 2,048 float32 numbers
x window layers (``flops_afmoe.window_cache_bytes``) over the chip's HBM
bandwidth, over the device time under ``attn.kv_write.window`` +
``attn.core.window`` + ``attn.gate.window`` in that span; median. The
program reads every slot's 4,224 rows."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.decode_attention_roofline_share(view, "window")
