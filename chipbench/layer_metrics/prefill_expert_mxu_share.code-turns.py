"""What the padded expert queues cost: the FLOPs of the ROUTED rows of one
prefill program (``num_experts_per_tok`` experts a token,
``flops_glm4.routed_expert_flops``) over the chip's bfloat16 peak, over the
device time under ``moe.experts`` in its ``uccl.wire.prefill`` span;
quotient program by program, median over the window's. A program's routed
rows are ``rows x chunk`` from the span's own arguments: since PR 28 the
program runs 1, 2 or the pool's rows (``rows``); a span without that
argument (a program before PR 28) is the pool's ``serving.slots``. At
capacity_factor 16 each of 64 queues holds as many rows as the program has
tokens, 16 computed for each one routed, and a one-row program sits on the
expert weights' read whatever its rows: about 3 % (4.7 % while every
program was the pool's)."""

from chipbench import flops_glm4
from chipbench import scopes_glm4 as sc
from chipbench.stats import percentile


def read(view):
    rows = sc.rows_in(view, sc.PREFILL)
    if not rows:
        return None
    s = view.cfg["serving"]
    shares = []
    for row in rows:
        ns = sum(row.get(scope, 0.0) for scope in sc.MOE_EXPERTS)
        if ns <= 0:
            continue
        args = row.get("args", {})
        tokens = (int(args.get("rows", s["slots"]))
                  * int(args.get("chunk", s["prefill_chunk"])))
        need = flops_glm4.routed_expert_flops(view.cfg, tokens)
        shares.append(100.0 * need / view.peaks["bf16_flops"] / (ns / 1e9))
    return percentile(shares, 50)
