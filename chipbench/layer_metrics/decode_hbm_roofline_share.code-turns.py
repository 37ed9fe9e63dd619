"""A decode step's share of its HBM roofline: the bytes the step must read
(``flops_glm4.decode_step_bytes``: bfloat16 attention, router, shared-
expert, dense-layer and head weights, the routed experts at least one
decoding row can reach, 576 float32 numbers a cached row in use) over the
chip's HBM bandwidth, over the step's device-busy time; median over the
traced decode steps.

Experts reached is the expectation for ``decoding`` rows of top-k draws
over E experts, E (1 - (1 - k/E)^rows) a layer — the program reports no
routing counts."""

from chipbench import flops_glm4
from chipbench.runners.serve import NAME_DECODE, NAME_STEP
from chipbench.stats import percentile


def read(view):
    tr = view.tr
    steps = [sp for sp in view.host_spans if sp[0] == NAME_STEP]
    dec = tr.busy_per_span(view.ops(0), view.host_spans, NAME_DECODE)
    dec_spans = [sp for sp in view.host_spans if sp[0] == NAME_DECODE]
    e = view.cfg["n_routed_experts"]
    k = view.cfg["num_experts_per_tok"]
    shares = []
    for (busy, _, _), sp in zip(dec, dec_spans):
        outer = [s for s in steps if s[1] <= sp[1] < s[1] + s[2]]
        if not outer or busy <= 0 or len(outer[0]) < 4:
            continue
        args = outer[0][3]
        rows = int(args.get("decoding", 0))
        if rows < 1:
            continue
        reached = e * (1.0 - (1.0 - k / e) ** rows)
        need = flops_glm4.decode_step_bytes(view.cfg, reached,
                                            int(args.get("kv_rows", 0)))
        shares.append(100.0 * need / view.peaks["hbm_bytes_per_s"]
                      / (busy / 1e9))
    return percentile(shares, 50) if shares else None
