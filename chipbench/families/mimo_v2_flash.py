"""``mimo_v2_flash`` (MiMo-V2-Flash): the attention scopes carry the layer's
KIND (``attn.core.full`` / ``attn.core.window``, likewise ``attn.qkv``,
``attn.kv_write``, ``attn.out``), the leading dense layer's FFN is
``ffn.dense``; ``flops_mimo.py``'s counts. Where the cached rows are read:
the core, and the write's scope, under which the compiler also prepares a
layer's cached rows for the MXU."""

from chipbench import flops_mimo
from chipbench import program_trace as pt

KINDS = ("full", "window")
_PARTS = ("attn.qkv", "attn.kv_write", "attn.core", "attn.out")
ATTENTION = {kind: tuple(f"{p}.{kind}" for p in _PARTS) for kind in KINDS}
CACHE_READ = {kind: (f"attn.kv_write.{kind}", f"attn.core.{kind}")
              for kind in KINDS}
SCOPES = pt.SCOPES + ATTENTION["full"] + ATTENTION["window"] + ("ffn.dense",)
GROUPS = {
    "full_attention": ATTENTION["full"],
    "window_attention": ATTENTION["window"],
    "cache_read.full": CACHE_READ["full"],
    "cache_read.window": CACHE_READ["window"],
    "moe_experts": pt.MOE_EXPERTS,
    "moe_exchange": pt.MOE_EXCHANGE,
}
RING_POOL_GROUPS = ("window",)


def decode_step_bytes(cfg, facts):
    return flops_mimo.decode_step_bytes(cfg, int(facts["n"]),
                                        int(facts["kv_rows"]))


def full_cache_bytes(cfg, facts):
    return flops_mimo.full_cache_bytes(cfg, int(facts["kv_rows"]))


def window_cache_bytes(cfg, facts):
    return flops_mimo.window_cache_bytes(cfg, int(facts["n"]),
                                         int(facts["kv_rows"]))
