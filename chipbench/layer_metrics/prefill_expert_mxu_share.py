"""What the padded expert queues cost: the FLOPs of the ROUTED rows of one
prefill program (the family's ``routed_expert_flops`` of its ``rows x
chunk`` tokens, the ``uccl.wire.prefill`` span's own arguments) over the
chip's bfloat16 peak, over the device time under ``moe_experts`` in that
span; quotient program by program, median over the window's."""
from chipbench.scopes import prefill_expert_mxu_share as read  # noqa: F401
