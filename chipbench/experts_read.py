"""The share of the experts held that a decode program's expert GEMMs read,
from the program's own reading: ``SlotBackend`` puts each step's count
(the decode program's output, fetched with its tokens) and what the step
holds — experts held x expert layers — on an empty ``uccl.ep.experts``
span inside the step's ``uccl.wire.decode`` (arguments ``experts_read``,
``experts_held``; docs/OBSERVABILITY.md). 100 % is a program that skips
nothing; a program that reports no count (the parent of the PR that added
it) gives ``None``."""

from typing import Optional

from chipbench import program_trace as pt
from chipbench.stats import percentile

EXPERTS = pt.PREFIX + "ep.experts"


def decode_experts_read_share(view) -> Optional[float]:
    """Median, over the window's ``uccl.wire.decode`` spans, of experts
    read over experts held, in %."""
    loaded = pt._loaded(view)
    if loaded is None:
        return None
    counts = pt.spans_in(loaded.spans, EXPERTS, *view.window)
    shares, k = [], 0
    for _, start, dur, _ in pt.spans_in(loaded.spans, pt.DECODE,
                                        *view.window):
        # both lists are in time order: the step's count is the first that
        # starts inside its span
        while k < len(counts) and counts[k][1] < start:
            k += 1
        if k < len(counts) and counts[k][1] <= start + dur:
            args = counts[k][3]
            if float(args.get("experts_held", 0)) > 0:
                shares.append(100.0 * float(args["experts_read"])
                              / float(args["experts_held"]))
    return percentile(shares, 50) if shares else None
