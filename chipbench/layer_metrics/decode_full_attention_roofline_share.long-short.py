"""The full layers' attention core's share of its HBM roofline in a decode
program: the cached rows in use (``kv_rows`` of the ``uccl.wire.decode``
span) x 1,280 float32 numbers x full layers
(``flops_mimo.full_cache_bytes``) over the chip's HBM bandwidth, over the
device time under ``attn.kv_write.full`` + ``attn.core.full`` in that span
(the compiler prepares a layer's cached rows for the MXU under the write's
scope); median. The program reads the whole group, not the rows in use:
this share says what that costs."""

from chipbench import scopes_mimo as sc


def read(view):
    return sc.decode_attention_roofline_share(view, "full")
