"""A request's wait for its first token by what the device did in it. The
engine marks ``uccl.admit`` (the request has its slot) and
``uccl.first_token`` with the request's ``rid``; between the two the chip
ran prefill programs (the request's own chunks and its neighbours'), decode
programs (the other slots' tokens, one a chunk step), now and then another
program, and for the rest it was idle (the host's turn of every step). The
intervals of all requests admitted and given a first token inside the
window are summed and each kind's time given as a share of the sum, in %.

The two sides are taken from different lines of the chip's plane. A kind's
device time is its program runs' spans, first operation to last
(``step_timeline.Timeline.runs``, the ``XLA Modules`` line); idle time is
the gaps between operations (``Timeline.busy``, the ``XLA Ops`` line, as
``device_idle_share`` takes it). So the four need not add up to 100, and
what is missing is reported as ``residual``: above 0 where an operation ran
outside every program run, under 0 where a run held a gap between its
operations (counted as the run's and as idle). Both lines are on the host's
clock by the lead ``step_timeline`` finds; without one, or on a program
that writes no marks, every reading is ``None``."""

from __future__ import annotations

from bisect import bisect_right
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import program_trace as pt
from chipbench import step_timeline as st

ADMIT = pt.PREFIX + "admit"
FIRST_TOKEN = pt.PREFIX + "first_token"
KINDS = ("prefill", "decode", "other", "idle")
RESIDUAL = "residual"  # 100 - the four


def first_token_waits(spans: Sequence[tuple], t0: float, t1: float
                      ) -> List[Tuple[int, float, float]]:
    """(rid, admitted, first token) of each request whose ``uccl.admit`` and
    ``uccl.first_token`` marks, paired by ``rid``, both lie in [t0, t1). A
    request admitted before the window, or still waiting when it closes, is
    left out."""
    admitted: Dict[int, float] = {}
    out = []
    for sp in spans:
        if sp[0] not in (ADMIT, FIRST_TOKEN) or not t0 <= sp[1] < t1 \
                or len(sp) < 4 or "rid" not in sp[3]:
            continue
        rid = int(sp[3]["rid"])
        if sp[0] == ADMIT:
            admitted.setdefault(rid, sp[1])
        elif rid in admitted:
            out.append((rid, admitted.pop(rid), sp[1]))
    return out


def kind_of(program: str) -> str:
    if st.PREFILL_PROGRAM.match(program):
        return "prefill"
    return "decode" if st.STEP_PROGRAM.match(program) else "other"


def wait_shares(waits: Sequence[Tuple[int, float, float]],
                runs: Sequence[st.Run], busy: Sequence[Tuple[float, float]]
                ) -> Optional[Dict[str, float]]:
    """{kind: % of the waits' summed length} over ``KINDS``, and
    ``RESIDUAL``. ``runs`` (by start, none overlapping another: one chip
    runs one program at a time) and ``busy`` (the merged intervals in which
    an operation ran) on the waits' clock. Idle time is walked gap by gap
    over ``busy``, not taken as what the runs leave."""
    total = sum(b - a for _, a, b in waits)
    if total <= 0:
        return None
    run_ends = [r[2] for r in runs]
    busy_ends = [hi for _, hi in busy]
    ns = dict.fromkeys(KINDS, 0.0)
    for _, a, b in waits:
        i = bisect_right(run_ends, a)  # the first run that ends after ``a``
        while i < len(runs) and runs[i][1] < b:
            ns[kind_of(runs[i][0])] += min(b, runs[i][2]) - max(a, runs[i][1])
            i += 1
        cur, i = a, bisect_right(busy_ends, a)
        while i < len(busy) and busy[i][0] < b:
            ns["idle"] += max(0.0, busy[i][0] - cur)
            cur = max(cur, busy[i][1])
            i += 1
        ns["idle"] += max(0.0, b - cur)
    shares = {k: 100.0 * v / total for k, v in ns.items()}
    shares[RESIDUAL] = 100.0 - sum(shares.values())
    return shares


def shares_in(view) -> Optional[Dict[str, float]]:
    """:func:`wait_shares` of a traced run's view."""
    t = st.of(view)
    if t is None or t.runs is None:
        return None
    spans = pt.load(view.record["trace_path"]).spans
    return wait_shares(first_token_waits(spans, *view.window), t.runs,
                       t.busy)


def share(view, kind: str) -> Optional[float]:
    """A reader's whole body."""
    shares = shares_in(view)
    return None if shares is None else shares[kind]
