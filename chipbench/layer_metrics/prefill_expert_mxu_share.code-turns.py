"""What the padded expert queues cost: the FLOPs of the ROUTED rows of one
``[slots, chunk]`` prefill program (``num_experts_per_tok`` experts a
token, ``flops_glm4.routed_expert_flops``) over the chip's bfloat16 peak,
over the device time under ``moe.experts`` in a ``uccl.wire.prefill``
span; median over the window's spans. At capacity_factor 16 each of 64
queues holds as many rows as the program has tokens, 16 rows computed for
each one routed, so this reads about a sixteenth of what the GEMMs reach."""

from chipbench import flops_glm4
from chipbench import scopes_glm4 as sc


def read(view):
    ms = sc.scope_ms_in(view, sc.PREFILL, sc.MOE_EXPERTS)
    if not ms:
        return None
    s = view.cfg["serving"]
    need = flops_glm4.routed_expert_flops(
        view.cfg, s["slots"] * s["prefill_chunk"])
    return 100.0 * need / view.peaks["bf16_flops"] / (ms / 1e3)
