"""``mixtral``: the first model's twelve scopes (``program_trace.SCOPES``),
every layer a full attention layer under the un-suffixed ``attn.*``, and
``flops.py``'s counts. The program reports the experts a step read, but
``flops.decode_step_bytes`` is handed the EXPECTATION for the step's ``n``
rows of top-k draws over E experts, E (1 - (1 - k/E)^n), as since PR 23."""

from chipbench import flops
from chipbench import program_trace as pt

SCOPES = pt.SCOPES
GROUPS = {
    "full_attention": pt.ATTENTION,
    "moe_experts": pt.MOE_EXPERTS,
    "moe_exchange": pt.MOE_EXCHANGE,
}


def decode_step_bytes(cfg, facts):
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    reached = e * (1.0 - (1.0 - k / e) ** int(facts["n"]))
    return flops.decode_step_bytes(cfg, reached, int(facts["kv_rows"]))
