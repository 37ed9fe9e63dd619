"""Everything ``program_trace.py`` reads from one trace, with the two sums
that must hold, as one JSON object:

    python -m chipbench.tools.scope_report <xplane.pb> [out.json] [--family <model_type>]

(a) per program, the device time under every scope of the family
(``chipbench/families/<model_type>.py``; the first model's where none is
named) plus the unscoped rest, and the span's operations as one union,
against the benchmark's own ``decode_step_dev_ms`` / ``prefill_step_dev_ms``
reduction; (b) idle time by innermost program span, summed, against the
window's idle time. Also what is under no scope, by operation, and how many
programs a backend call launches. By hand, after a change to the program's
scopes or spans."""

import argparse
import json

from chipbench import families
from chipbench import program_trace as pt
from chipbench import trace_reduce as tr
from chipbench.runners.serve import NAME_DECODE, NAME_PREFILL
from chipbench.stats import percentile


def report(path: str, model_type: str = "mixtral") -> dict:
    scopes = tuple(families.named(model_type).SCOPES)
    trace = tr.load_xplane(path, everything=True)
    win = tr.window(trace)
    loaded = pt.load(path)
    ops = tr.clip(loaded.ops[0], *win)
    host = tr.host_spans(trace)
    chip = tr.device_planes(trace)[0]
    unaligned = tr.clip(tr.line_events(chip, tr.OPS_LINE), *win)
    out = {"window_s": (win[1] - win[0]) / 1e9,
           "device_clock_lead_ms": [x / 1e6 for x in
                                    loaded.lead_bounds_ns or ()],
           "programs": {}}
    for label, span, outside in (("decode", pt.DECODE, NAME_DECODE),
                                 ("prefill", pt.PREFILL, NAME_PREFILL)):
        spans = pt.spans_in(loaded.spans, span, *win)
        groups = [g for g in tr.events_inside(ops, spans, span) if g]
        rows = [pt.by_scope(g, scopes) for g in groups]
        old = [b for b, _, _ in tr.busy_per_span(unaligned, host, outside)
               if b > 0]
        seen = sorted({s for r in rows for s in r}, key=str)
        out["programs"][label] = {
            "spans": len(rows),
            "by_scope_ms": {str(s): percentile([r.get(s, 0.0) for r in rows],
                                               50) / 1e6 for s in seen},
            "all_scopes_and_rest_ms": percentile(
                [sum(r.values()) for r in rows], 50) / 1e6 if rows else None,
            "union_ms": percentile([tr.busy_ns(g) for g in groups],
                                   50) / 1e6 if groups else None,
            "benchmark_span_ms": percentile(old, 50) / 1e6 if old else None,
        }
    idle = {name: sec * 1e9 for name, sec in tr.idle_gaps(
        loaded.ops[0], *win, loaded.spans, n=1 << 30)}
    steps = len(pt.spans_in(loaded.spans, pt.STEP, *win))
    out["steps"] = steps
    out["idle_s_by_innermost_span"] = {k: v / 1e9 for k, v in sorted(
        idle.items(), key=lambda kv: -kv[1])}
    out["idle_s_summed"] = sum(idle.values()) / 1e9
    out["idle_s_window"] = out["window_s"] - tr.busy_ns(ops) / 1e9
    bare = {}
    for ev in ops:
        if pt.scope_of(ev[3], scopes) is None:
            key = tr.short_op_name(ev[0]) + (" | " + ev[3] if ev[3] else "")
            bare[key] = bare.get(key, 0.0) + ev[2]
    out["unscoped_share_pct"] = pt.unscoped_share(ops, scopes)
    out["unscoped_top_s"] = [[k, v / 1e9] for k, v in sorted(
        bare.items(), key=lambda kv: -kv[1])[:12]]
    mods = tr.clip(tr.line_events(chip, tr.MODULES_LINE), *win)
    runs = {}
    for ev in mods:
        name = ev[0].split("(")[0]
        runs[name] = runs.get(name, 0) + 1
    out["program_runs_in_window"] = runs
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("out", nargs="?")
    ap.add_argument("--family", default="mixtral")
    args = ap.parse_args()
    text = json.dumps(report(args.path, args.family), indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
