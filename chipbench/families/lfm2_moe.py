"""``lfm2_moe`` (LFM2-24B-A2B): beside the full attention layers' four
scopes (``attn.*.full``, as ``mimo_v2_flash`` names them) a conv layer's
operator has four of its own: ``conv.in_proj`` (the three gates' projection
and ``y = b * u``), ``conv.state`` (the ring write and the read of the
positions before the call), ``conv.mix`` (the taps and the ``c`` gate) and
``conv.out_proj``. ``flops_lfm2.py``'s counts; the experts a step read are
the program's own count (the step's ``uccl.ep.experts`` span), and a step
without one has no ``decode_step_bytes``."""

from chipbench import flops_lfm2
from chipbench import program_trace as pt
from chipbench.families import mimo_v2_flash as mimo

CONV = ("conv.in_proj", "conv.state", "conv.mix", "conv.out_proj")
SCOPES = pt.SCOPES + mimo.ATTENTION["full"] + CONV + ("ffn.dense",)
GROUPS = {
    "full_attention": mimo.ATTENTION["full"],
    "cache_read.full": mimo.CACHE_READ["full"],
    "conv": CONV,
    "moe_experts": pt.MOE_EXPERTS,
    "moe_exchange": pt.MOE_EXCHANGE,
}
RING_POOL_GROUPS = ("conv",)
routed_expert_flops = flops_lfm2.routed_expert_flops


def decode_step_bytes(cfg, facts):
    if facts.get("experts_read") is None:
        return None
    return flops_lfm2.decode_step_bytes(
        cfg, int(facts["n"]), int(facts["kv_rows"]),
        float(facts["experts_read"]))


def full_cache_bytes(cfg, facts):
    return flops_lfm2.full_cache_bytes(cfg, int(facts["kv_rows"]))


def conv_decode_bytes(cfg, facts):
    return flops_lfm2.conv_decode_bytes(cfg, int(facts["n"]))
