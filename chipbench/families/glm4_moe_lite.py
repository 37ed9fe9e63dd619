"""``glm4_moe_lite`` (GLM-4.7-Flash): the first model's twelve scopes and
four more — ``attn.latent_q`` / ``attn.latent_kv`` (latent attention's
down-projections and norms; its up-projections stay under ``attn.qkv`` /
``attn.out``, its absorbed core under ``attn.core``), ``moe.shared`` (the
shared expert) and ``ffn.dense`` (the leading dense layer's FFN) — with
``flops_glm4.py``'s counts. Experts reached is the expectation for the
step's ``n`` rows, E (1 - (1 - k/E)^n) a layer."""

from chipbench import flops_glm4
from chipbench import program_trace as pt

SCOPES = pt.SCOPES + ("attn.latent_q", "attn.latent_kv", "moe.shared",
                      "ffn.dense")
GROUPS = {
    "latent_attention": pt.ATTENTION + ("attn.latent_q", "attn.latent_kv"),
    "cache_read.latent": ("attn.core",),
    "shared_dense_ffn": ("moe.shared", "ffn.dense"),
    "moe_experts": pt.MOE_EXPERTS,
    "moe_exchange": pt.MOE_EXCHANGE,
}
routed_expert_flops = flops_glm4.routed_expert_flops


def decode_step_bytes(cfg, facts):
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    reached = e * (1.0 - (1.0 - k / e) ** int(facts["n"]))
    return flops_glm4.decode_step_bytes(cfg, reached, int(facts["kv_rows"]))


def latent_cache_bytes(cfg, facts):
    return flops_glm4.latent_cache_bytes(cfg, int(facts["kv_rows"]))
