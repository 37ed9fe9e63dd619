"""``afmoe`` (Trinity): ``mimo_v2_flash``'s scopes by layer kind with one
more part a kind, the output's gate (``attn.gate.full`` /
``attn.gate.window``: the gate's projection, its sigmoid and the product;
since PR 41 the decode program's lies fused under ``attn.out.<kind>``);
QK-norm lies under ``attn.qkv.*`` and the attention branch's closing norm
under ``attn.out.*``. Beside them ``moe.shared`` (the shared expert) and
``ffn.post_norm`` (the FFN branch's closing norm). ``flops_afmoe.py``'s
counts; a decode span without ``window_rows`` (a program before it) is
given its upper bound, ``min(n x sliding_window, kv_rows)``."""

from chipbench import flops_afmoe
from chipbench import program_trace as pt
from chipbench.families import mimo_v2_flash as mimo

GATE = {kind: (f"attn.gate.{kind}",) for kind in mimo.KINDS}
ATTENTION = {kind: mimo.ATTENTION[kind] + GATE[kind] for kind in mimo.KINDS}
SCOPES = mimo.SCOPES + GATE["full"] + GATE["window"] + ("moe.shared",
                                                        "ffn.post_norm")
GROUPS = {
    "full_attention": ATTENTION["full"],
    "window_attention": ATTENTION["window"],
    # what the attended rows pass before the output projection counts too
    "cache_read.full": mimo.CACHE_READ["full"] + GATE["full"],
    "cache_read.window": mimo.CACHE_READ["window"] + GATE["window"],
    "moe_experts": pt.MOE_EXPERTS,
    "moe_exchange": pt.MOE_EXCHANGE,
    "moe_shared": ("moe.shared",),
}
RING_POOL_GROUPS = ("window",)
routed_expert_flops = flops_afmoe.routed_expert_flops


def _window_rows(cfg, facts) -> int:
    return int(facts.get("window_rows", min(
        int(facts["n"]) * cfg["sliding_window"], int(facts["kv_rows"]))))


def decode_step_bytes(cfg, facts):
    return flops_afmoe.decode_step_bytes(
        cfg, int(facts["n"]), int(facts["kv_rows"]), _window_rows(cfg, facts))


def full_cache_bytes(cfg, facts):
    return flops_afmoe.full_cache_bytes(cfg, int(facts["kv_rows"]))


def window_cache_bytes(cfg, facts):
    return flops_afmoe.window_cache_bytes(cfg, _window_rows(cfg, facts))
