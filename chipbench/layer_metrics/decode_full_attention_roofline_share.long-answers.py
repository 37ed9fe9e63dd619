"""The attention layers' share of their HBM roofline over the cache in a
decode program: the cached rows in use (``kv_rows`` of the
``uccl.wire.decode`` span) x 1,024 float32 numbers x 2 layers
(``flops_lfm2.full_cache_bytes``) over the chip's HBM bandwidth, over the
device time under ``attn.kv_write.full`` + ``attn.core.full`` in that span;
median. The program reads every slot's whole ``S_max``, not the rows in
use: this share says what that costs."""

from chipbench import scopes_lfm2 as sc


def read(view):
    return sc.decode_full_attention_roofline_share(view)
