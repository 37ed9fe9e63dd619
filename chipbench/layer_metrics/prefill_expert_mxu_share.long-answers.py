"""What the padded expert queues cost: the FLOPs of the ROUTED rows of one
prefill program (4 experts a token, ``flops_lfm2.routed_expert_flops``)
over the chip's bfloat16 peak, over the device time under ``moe.experts``
in its ``uccl.wire.prefill`` span; quotient program by program, median over
the window's. At capacity_factor 16 each of the 64 queues holds as many
rows as the program has tokens: 16 computed for each one routed."""

from chipbench import scopes_lfm2 as sc


def read(view):
    return sc.prefill_expert_mxu_share(view)
