"""Everything ``step_timeline.py`` and ``request_timeline.py`` read from one
trace, with the sums that must hold, as one JSON object:

    python -m chipbench.tools.timeline_report <xplane.pb> [out.json]

(a) the four parts of a decode-only period — dispatch, the step's program on
the chip, completion, the host between calls — each a median, and the
median period of the same pairs of calls (launch opens to the next launch
opens), over the calls and over their token gaps (a call with ``n``
slots decoding is ``n`` gaps: what a traced run's ``itl_p50_ms`` is the
median of, beside the few gaps behind a prefill program: the period by
gap against that ``itl_p50_ms`` is the check that the decomposition is
whole; the four parts and the period by call are one identity a call); (b)
the first-token shares with the share of any other program and the
``residual`` that the four, taken from two lines of the chip's plane, leave
of 100;
(c) the lead's bounds from the runtime's events and from the calls as
``step_timeline`` takes them, and the strict ones ``program_trace.load``
applied (empty: it fell back to a lead of 0). By hand, after a traced
run."""

import json
import sys

from chipbench import program_trace as pt
from chipbench import request_timeline as rt
from chipbench import step_timeline as st
from chipbench import trace_reduce as tr
from chipbench.stats import percentile


def _ms(values, q=50):
    return percentile(values, q) / 1e6 if values else None


def report(path: str) -> dict:
    trace = tr.load_xplane(path)
    win = tr.window(trace)
    loaded = pt.load(path)
    chip = tr.device_planes(trace)[0]
    modules = tr.line_events(chip, tr.MODULES_LINE)
    _, *runtime = pt.host_events(path)

    def ms(bounds):
        return [x / 1e6 for x in bounds or ()]

    out = {"window_s": (win[1] - win[0]) / 1e9,
           "lead_ms_runtime": ms(st.lead_from_runtime(*runtime)),
           "lead_ms_calls": ms(st.lead_from_calls(
               loaded.spans, st.program_runs(modules), *win)),
           "lead_ms_strict": ms(loaded.lead_bounds_ns)}
    t = st.build(loaded.spans, modules, tr.line_events(chip, tr.OPS_LINE),
                 runtime, *win)
    out["lead_from"] = t.lead_from
    calls = [c for c in t.calls if c.own]
    pairs = st.back_to_back(t.calls, t.prefill_steps)
    parts = {
        "decode_dispatch_latency_ms": _ms([c.own[1] - c.launch
                                           for c in calls]),
        "step_program_span_ms": _ms([c.own[2] - c.own[1] for c in calls]),
        "decode_completion_latency_ms": _ms([c.fetched - c.own[2]
                                             for c in calls]),
        "host_between_calls_ms": _ms([b.launch - a.fetched
                                      for a, b in pairs]),
    }
    out.update(parts)
    out["decode_calls"], out["decode_only_pairs"] = len(t.calls), len(pairs)
    out["decode_only_period_ms"] = _ms([b.launch - a.launch
                                        for a, b in pairs])
    out["decode_only_period_by_gap_ms"] = _ms(
        [b.launch - a.launch for a, b in pairs for _ in range(b.n)])
    out["dispatch_p10_p90_ms"] = [_ms([c.own[1] - c.launch for c in calls], q)
                                  for q in (10, 90)]
    out["completion_p10_p90_ms"] = [_ms([c.fetched - c.own[2]
                                         for c in calls], q)
                                    for q in (10, 90)]
    counts = {}
    for c in t.calls:
        key = " + ".join(r[0].split("(")[0] for r in c.runs)
        counts[key] = counts.get(key, 0) + 1
    out["programs_in_a_decode_call"] = dict(sorted(
        counts.items(), key=lambda kv: -kv[1])[:6])
    waits = rt.first_token_waits(loaded.spans, *win)
    out["first_token_waits"] = len(waits)
    out["first_token_shares_pct"] = None if t.runs is None \
        else rt.wait_shares(waits, t.runs, t.busy)
    return out


if __name__ == "__main__":
    result = report(sys.argv[1])
    text = json.dumps(result, indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    print(text)
