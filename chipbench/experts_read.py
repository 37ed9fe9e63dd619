"""The share of the experts held that a decode program's expert GEMMs read,
from the program's own reading: ``SlotBackend`` puts each step's count
(the decode program's output, fetched with its tokens) and what the step
holds — experts held x expert layers — on an empty ``uccl.ep.experts``
span inside the step's ``uccl.wire.decode`` (arguments ``experts_read``,
``experts_held``; docs/OBSERVABILITY.md). 100 % is a program that skips
nothing; a program that reports no count (the parent of the PR that added
it) gives ``None``."""

from typing import List, Optional, Sequence

from chipbench import program_trace as pt
from chipbench.stats import percentile

EXPERTS = pt.PREFIX + "ep.experts"


def counts_of(spans: Sequence[tuple], counts: Sequence[tuple]
              ) -> List[Optional[dict]]:
    """For each of ``spans``, the arguments of the first of ``counts`` (the
    ``uccl.ep.experts`` spans) that starts inside it, or None; both lists in
    time order."""
    out, k = [], 0
    for _, start, dur, *_ in spans:
        while k < len(counts) and counts[k][1] < start:
            k += 1
        inside = k < len(counts) and counts[k][1] <= start + dur
        out.append(counts[k][3] if inside else None)
    return out


def decode_experts_read_share(view) -> Optional[float]:
    """Median, over the window's ``uccl.wire.decode`` spans, of experts
    read over experts held, in %."""
    loaded = pt._loaded(view)
    if loaded is None:
        return None
    counts = pt.spans_in(loaded.spans, EXPERTS, *view.window)
    steps = pt.spans_in(loaded.spans, pt.DECODE, *view.window)
    shares = [100.0 * float(args["experts_read"])
              / float(args["experts_held"])
              for args in counts_of(steps, counts)
              if args and float(args.get("experts_held", 0)) > 0]
    return percentile(shares, 50) if shares else None
