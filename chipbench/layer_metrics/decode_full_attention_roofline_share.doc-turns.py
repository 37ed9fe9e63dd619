"""The full layer's attention over the cache's share of its HBM roofline in
a decode program: the cached rows in use (``kv_rows`` of the
``uccl.wire.decode`` span) x 2,048 float32 numbers
(``flops_afmoe.full_cache_bytes``) over the chip's HBM bandwidth, over the
device time under ``attn.kv_write.full`` + ``attn.core.full`` +
``attn.gate.full`` in that span; median. The program reads the whole group,
not the rows in use: this share says what that costs."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.decode_attention_roofline_share(view, "full")
