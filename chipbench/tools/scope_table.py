"""Device time by scope, per program, under a family's own scope list:

    python -m chipbench.tools.scope_table <xplane.pb> <model_type>

``<model_type>`` names the family (``chipbench/families/<model_type>.py``:
its ``SCOPES``), as a configuration's file does. One JSON object: for the
decode programs and for the prefill programs by their ``rows`` argument, the
number of spans and the median device ms under each scope (``None``: under
none), their sum, the span's operations as one union, and the largest
operations under no scope. By hand, to see where a program's time lies
before PERF.md says so."""

import json
import sys

from chipbench import families
from chipbench import program_trace as pt
from chipbench import trace_reduce as tr
from chipbench.stats import percentile


def table(path: str, model_type: str) -> dict:
    scopes = tuple(families.named(model_type).SCOPES)
    win = tr.window(tr.load_xplane(path, everything=True))
    loaded = pt.load(path)
    ops = tr.clip(loaded.ops[0], *win)
    out = {"window_s": (win[1] - win[0]) / 1e9,
           "busy_s": tr.busy_ns(ops) / 1e9,
           "unscoped_share_pct": pt.unscoped_share(ops, scopes),
           "programs": {}}
    for label, span in (("decode", pt.DECODE), ("prefill", pt.PREFILL)):
        spans = pt.spans_in(loaded.spans, span, *win)
        groups = {}
        for sp, evs in zip(spans, tr.events_inside(ops, spans, span)):
            if evs:
                rows = (sp[3] if len(sp) > 3 else {}).get("rows", "")
                groups.setdefault(f"{label}{rows}", []).append(
                    ({str(s): ns for s, ns in
                      pt.by_scope(evs, scopes).items()}, tr.busy_ns(evs)))
        for name, rows in groups.items():
            names = sorted({s for by, _ in rows for s in by})
            out["programs"][name] = {
                "spans": len(rows),
                "by_scope_ms": {s: round(percentile(
                    [by.get(s, 0.0) for by, _ in rows], 50) / 1e6, 4)
                    for s in names},
                "sum_ms": round(percentile(
                    [sum(by.values()) for by, _ in rows], 50) / 1e6, 4),
                "union_ms": round(percentile(
                    [busy for _, busy in rows], 50) / 1e6, 4)}
    bare = {}
    for ev in ops:
        if pt.scope_of(ev[3], scopes) is None:
            key = tr.short_op_name(ev[0]) + (" | " + ev[3] if ev[3] else "")
            bare[key] = bare.get(key, 0.0) + ev[2]
    out["unscoped_top_s"] = [[k, round(v / 1e9, 4)] for k, v in sorted(
        bare.items(), key=lambda kv: -kv[1])[:12]]
    return out


if __name__ == "__main__":
    print(json.dumps(table(sys.argv[1], sys.argv[2]), indent=1))
