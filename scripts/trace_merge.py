#!/usr/bin/env python
"""Merge N per-process Chrome traces into one clock-aligned fleet trace.

Each ``--trace-out`` dump carries its process's clock metadata
(``otherData.clock``: ``wall_epoch_us`` — the wall time of its monotonic
ts 0 — and ``offset_us``, the process's estimated wall offset from the
fleet's reference clock, set by the disagg HELLO clock exchange). This
tool places every file on one timeline::

    aligned_ts = ts + (wall_epoch_us - offset_us) - min_base

gives each file its own pid (named from its ``process_name`` metadata),
keeps flow-event ids untouched (they derive from trace_ids, so s/f pairs
bind ACROSS files), and validates the result with named failures:

* every ``B`` has its ``E`` on the same pid/tid; ``X`` durations >= 0;
* every flow-finish (``f``) resolves a flow-start (``s``) with its id;
* causal order per trace_id after alignment: ``submit`` (the BEGIN mint)
  <= ``grant`` <= ``adopt`` <= ``finish`` wherever those events exist —
  i.e. no GRANT precedes its BEGIN once the clocks are aligned.

Exit is non-zero on any violation, so qa.sh/ci.yml can gate on it. The
summary counts *cross-process* requests: trace_ids whose events span >= 2
pids with a resolved flow pair (what ``check_obs --fleet`` asserts >= 1).

Usage: python scripts/trace_merge.py --out MERGED.json TRACE.json...
(stdlib-only — runnable before any dependency is installed).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict
from typing import Dict, List

# the cross-process causal chain (BEGIN <= GRANT <= FINAL in stream
# terms), in required timeline order; absent stages are skipped (a
# non-disagg trace has no grant/adopt). "finish" stays OUT: the prefill
# fleet's local 1-token request legitimately finishes before the decode
# side adopts, so only the stream's own stages are globally ordered.
CAUSAL_ORDER = ("submit", "grant", "adopt")


def fail(msg: str) -> None:
    print(f"trace_merge: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def warn(msg: str) -> None:
    print(f"trace_merge: WARNING — {msg}", file=sys.stderr)


def load_trace(path: str, strict: bool = False) -> Dict:
    """Load one per-process trace. A trace without the clock-handshake
    record (``otherData.clock.wall_epoch_us``) is *unanchored*: under
    ``--strict`` that is fatal, otherwise it is merged UNADJUSTED (its
    timestamps keep their own epoch) with a warning — a partial fleet
    view beats crashing out of the whole merge when one worker died
    before its clock exchange."""
    with open(path) as f:
        trace = json.load(f)
    if not isinstance(trace.get("traceEvents"), list):
        fail(f"{path}: no traceEvents list")
    clock = trace.get("otherData", {}).get("clock")
    anchored = isinstance(clock, dict) and "wall_epoch_us" in clock
    if not anchored:
        if strict:
            fail(f"{path}: no otherData.clock.wall_epoch_us — cannot "
                 f"align an unanchored trace (--strict)")
        warn(f"{path}: no otherData.clock.wall_epoch_us — merging "
             f"UNADJUSTED (its timeline may not align with the anchored "
             f"files; cross-file causal checks are skipped)")
    trace["_anchored"] = anchored
    return trace


def process_name_of(trace: Dict, path: str) -> str:
    for ev in trace["traceEvents"]:
        if ev.get("name") == "process_name" and ev.get("ph") == "M":
            return str(ev.get("args", {}).get("name", ""))
    return os.path.splitext(os.path.basename(path))[0]


def merge_traces(paths: List[str], strict: bool = False) -> Dict:
    """Load, align and concatenate; returns the merged trace dict
    (validation is separate — :func:`validate_merged`). Unanchored files
    (no clock handshake) merge with shift 0 — their own timeline —
    unless ``strict`` makes that fatal."""
    traces = [load_trace(p, strict=strict) for p in paths]
    # per-file alignment base: wall anchor corrected by the process's
    # estimated offset from the reference clock (0 when never synced);
    # None for an unanchored file — it cannot participate in alignment
    bases = []
    for p, t in zip(paths, traces):
        if not t["_anchored"]:
            bases.append(None)
            continue
        clock = t["otherData"]["clock"]
        bases.append(float(clock["wall_epoch_us"])
                     - float(clock.get("offset_us", 0.0)))
    anchored_bases = [b for b in bases if b is not None]
    t0 = min(anchored_bases) if anchored_bases else 0.0
    out: List[Dict] = []
    meta = {"merged_from": [], "producer": "uccl_tpu trace_merge",
            # the wall epoch (us) of the merged timeline's ts 0 — what
            # `doctor --trace` uses to place flight bundles on this
            # timeline; 0.0 when every input was unanchored
            "merged_wall_epoch_us": t0}
    for i, (path, trace, base) in enumerate(zip(paths, traces, bases)):
        pid = i + 1
        shift = (base - t0) if base is not None else 0.0
        name = process_name_of(trace, path)
        meta["merged_from"].append({
            "path": path, "pid": pid, "process_name": name,
            "shift_us": round(shift, 3),
            "anchored": trace["_anchored"],
            "clock": trace["otherData"].get("clock"),
            "dropped_events": trace["otherData"].get("dropped_events", 0),
        })
        for ev in trace["traceEvents"]:
            ev = dict(ev)
            ev["pid"] = pid
            if ev.get("ph") != "M" and "ts" in ev:
                ev["ts"] = round(ev["ts"] + shift, 3)
            out.append(ev)
    out.sort(key=lambda ev: (ev.get("ts", -1.0), ev["pid"]))
    return {"traceEvents": out, "displayTimeUnit": "ms",
            "otherData": meta}


def validate_merged(merged: Dict) -> Dict:
    """Named-failure validation of a merged trace; returns summary stats
    (events, trace_ids, cross-process request count)."""
    evs = merged["traceEvents"]
    # pids merged without a clock anchor sit on their own timeline —
    # cross-clock causal order is meaningless for chains touching them
    unanchored_pids = {
        m["pid"] for m in merged["otherData"].get("merged_from", ())
        if not m.get("anchored", True)
    }
    flows: Dict[str, Dict] = defaultdict(lambda: {"s": [], "f": []})
    by_trace: Dict[str, List[Dict]] = defaultdict(list)
    for ev in evs:
        ph = ev.get("ph")
        if ph == "X" and ev.get("dur", 0) < 0:
            fail(f"X event {ev['name']!r} with negative dur after merge")
        if ph in ("s", "f"):
            flows[str(ev.get("id"))][ph].append(ev)
        tid = (ev.get("args") or {}).get("trace_id")
        if tid:
            by_trace[tid].append(ev)
    for fid, sf in flows.items():
        if sf["f"] and not sf["s"]:
            fail(f"flow id {fid}: finish without a start — the s/f pair "
                 f"did not resolve across the merged files")
    # causal order per trace_id on the ALIGNED timeline (skipped for
    # chains that touch an unanchored pid — their ts were never aligned)
    skipped_causal = 0
    for tid, tevs in by_trace.items():
        if unanchored_pids and any(
                ev["pid"] in unanchored_pids for ev in tevs):
            skipped_causal += 1
            continue
        stages = {}
        for ev in tevs:
            n = ev["name"]
            if n in CAUSAL_ORDER and n not in stages:
                stages[n] = ev
        chain = [stages[n] for n in CAUSAL_ORDER if n in stages]
        for a, bnext in zip(chain, chain[1:]):
            if a["ts"] > bnext["ts"]:
                fail(f"trace {tid}: {bnext['name']!r} "
                     f"(pid {bnext['pid']}, ts {bnext['ts']}) precedes "
                     f"{a['name']!r} (pid {a['pid']}, ts {a['ts']}) after "
                     f"clock alignment — causal order violated")
    cross = 0
    for tid, tevs in by_trace.items():
        pids = {ev["pid"] for ev in tevs}
        if len(pids) < 2:
            continue
        # the flow pair derived from this trace_id (obs.flow_id rule),
        # resolved with its start and finish on DIFFERENT processes
        try:
            fid = str(int(tid[:15], 16))
        except ValueError:
            continue
        sf = flows.get(fid)
        if (sf and sf["s"] and sf["f"]
                and {ev["pid"] for ev in sf["s"]}
                != {ev["pid"] for ev in sf["f"]}):
            cross += 1
    stats = {"events": len(evs), "trace_ids": len(by_trace),
             "cross_process_requests": cross}
    if unanchored_pids:
        stats["unanchored_files"] = len(unanchored_pids)
        stats["causal_checks_skipped"] = skipped_causal
        warn(f"{len(unanchored_pids)} unanchored file(s) merged "
             f"unadjusted; causal order skipped for {skipped_causal} "
             f"trace id(s)")
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Merge per-process Chrome traces into one "
                    "clock-aligned fleet trace (validated).",
    )
    ap.add_argument("inputs", nargs="+", help="per-process trace JSONs")
    ap.add_argument("--out", required=True, help="merged trace path")
    ap.add_argument("--strict", action="store_true",
                    help="fail (exit 1) on a trace missing the clock "
                         "handshake instead of merging it unadjusted "
                         "with a warning")
    args = ap.parse_args(argv)
    if len(args.inputs) < 2:
        fail("need >= 2 traces to merge")
    merged = merge_traces(args.inputs, strict=args.strict)
    stats = validate_merged(merged)
    merged["otherData"]["stats"] = stats
    with open(args.out, "w") as f:
        json.dump(merged, f)
    print(f"trace_merge: OK — {len(args.inputs)} files, "
          f"{stats['events']} events, {stats['trace_ids']} trace id(s), "
          f"{stats['cross_process_requests']} cross-process request(s) "
          f"-> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
