"""What the padded expert queues cost: the FLOPs of the rows ROUTED to the 32
held experts in one prefill program (4 draws a token of which 32 / 256 land
here, ``flops_afmoe.routed_expert_flops``) over the chip's bfloat16 peak,
over the device time under ``moe.experts`` in its ``uccl.wire.prefill``
span; quotient program by program, median over the window's. At
capacity_factor 64 each of the 32 queues holds as many rows as the program
has tokens: 64 computed for each one routed."""

from chipbench import scopes_afmoe as sc


def read(view):
    return sc.prefill_expert_mxu_share(view)
