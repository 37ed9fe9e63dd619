"""From a profiler trace to numbers: pure functions over a neutral trace
structure, so every PR reduces the same way and the arithmetic can be
checked on a small recorded trace (``chipbench/tests/fixtures``).

A trace is ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, dur_ns], ...]}]}]}`` — what ``jax.profiler.ProfileData``
holds, and nothing else. On a TPU each chip is a plane ``/device:TPU:<i>``
whose ``XLA Ops`` line carries one event per executed HLO operation and
whose ``XLA Modules`` line one per executed program; host threads are lines
of the ``/host:CPU`` plane, where ``jax.profiler.TraceAnnotation`` spans
appear under their own names. Both are on one clock.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple  # (name, start_ns, dur_ns[, arguments of an annotation])

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "chipbench.window"
COLLECTIVE_KINDS = ("all-to-all", "all-reduce", "reduce-scatter",
                    "all-gather", "collective-permute",
                    "ragged-all-to-all")


def load_xplane(path: str, everything: bool = False) -> dict:
    """Read an ``.xplane.pb`` with nothing but JAX: the chips' operation
    and program lines and, of the host threads, the benchmark's own
    annotations (``everything`` keeps every line and event, to look at a new
    kind of trace by hand)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and not everything and line.name not in (OPS_LINE,
                                                               MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                if not (device or everything
                        or ev.name.startswith("chipbench.")):
                    continue
                row = [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                if ev.name.startswith("chipbench."):
                    # the benchmark's own annotations carry their arguments
                    row.append({k: v for k, v in ev.stats
                                if isinstance(v, (int, float, str))})
                events.append(row)
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> List[dict]:
    planes = [p for p in trace["planes"]
              if re.fullmatch(r"/device:TPU:\d+", p["name"])]
    return sorted(planes, key=lambda p: int(p["name"].rsplit(":", 1)[1]))


def line_events(plane: dict, line_name: str) -> List[Event]:
    out: List[Event] = []
    for line in plane["lines"]:
        if line["name"] == line_name:
            out.extend(tuple(e) for e in line["events"])
    return sorted(out, key=lambda e: e[1])


def host_spans(trace: dict, prefix: str = "chipbench.") -> List[Event]:
    """The benchmark's own annotations on the host threads."""
    out: List[Event] = []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            out.extend(tuple(e) for e in line["events"]
                       if e[0].startswith(prefix))
    return sorted(out, key=lambda e: e[1])


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to [t0, t1]."""
    out = []
    for ev in events:
        name, s, d = ev[:3]
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a) + tuple(ev[3:]))
    return out


def merged_intervals(events: Iterable[Event]) -> List[Tuple[float, float]]:
    ivs = sorted((e[1], e[1] + e[2]) for e in events if e[2] > 0)
    out: List[List[float]] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    return sum(b - a for a, b in merged_intervals(events))


def overlap_ns(ivs: Sequence[Tuple[float, float]], a: float, b: float) -> float:
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in ivs)


_OP = re.compile(r"^(%[\w.\-]+) = \(?(\w+\[[\d,]*\])?[^ ]* ?.*?([a-z][\w\-]*)\(")
_PARAM = re.compile(r"%p__([A-Za-z_]+?)__*\.?\d*[,)]")


def short_op_name(name: str) -> str:
    """An HLO operation's trace name (its whole text) cut to what tells it
    apart: name, result shape, opcode, and the parameters it reads —
    ``%fusion.11 f32[8,32,4096] fusion <blocks_we_up>``."""
    m = _OP.match(name)
    if not m:
        return name[:96]
    params = sorted({re.sub("_+", "_", q).strip("_")
                     for q in _PARAM.findall(name)})
    out = " ".join(x for x in (m.group(1), m.group(2), m.group(3)) if x)
    return out + (" <" + ",".join(params) + ">" if params else "")


def top_ops(events: Iterable[Event], n: int = 10) -> List[List]:
    """The n operations that took most device time, under their short
    names: [[name, s], ...]."""
    total: Dict[str, float] = {}
    for ev in events:
        key = short_op_name(ev[0])
        total[key] = total.get(key, 0.0) + ev[2]
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


def innermost_segments(spans: Sequence[Event]) -> List[Tuple[float, float, str]]:
    """The timeline cut at every span boundary, each piece named after the
    innermost (the shortest) span that covers it: [(start, end, name), ...]
    in order, pieces no span covers left out."""
    marks = sorted({x for sp in spans for x in (sp[1], sp[1] + sp[2])})
    starts = sorted(spans, key=lambda sp: sp[1])
    out, active, k = [], [], 0
    for lo, hi in zip(marks, marks[1:]):
        while k < len(starts) and starts[k][1] <= lo:
            active.append(starts[k])
            k += 1
        active = [sp for sp in active if sp[1] + sp[2] >= hi]
        if active:
            out.append((lo, hi, min(active, key=lambda sp: sp[2])[0]))
    return out


def idle_gaps(events: Iterable[Event], t0: float, t1: float,
              spans: Sequence[Event], n: int = 10) -> List[List]:
    """Device idle time inside [t0, t1], by what the host was doing: each
    piece of a gap between busy intervals is charged to the innermost (the
    shortest) host span that covers it, the traced window's own span aside,
    and to "(no span)" where none does. Returns the n names with most idle
    time: [[name, s], ...]."""
    busy = merged_intervals(clip(events, t0, t1))
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    segments = innermost_segments([sp for sp in spans if sp[0] != WINDOW_SPAN])
    total: Dict[str, float] = {}
    k = 0
    for a, b in gaps:  # both lists are in time order: one pass over each
        while k < len(segments) and segments[k][1] <= a:
            k += 1
        covered, j = 0.0, k
        while j < len(segments) and segments[j][0] < b:
            lo, hi, name = segments[j]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                total[name] = total.get(name, 0.0) + part
                covered += part
            j += 1
        if b - a > covered:
            total["(no span)"] = total.get("(no span)", 0.0) + (b - a - covered)
    rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


def opcode(name: str) -> str:
    """The HLO opcode of an operation's trace name (its whole text, ``%x =
    <type> <opcode>(...)``); the bare name where it has no such form."""
    m = _OP.match(name)
    return m.group(3) if m else name.lstrip("%").split(" ")[0]


def is_collective(name: str, kinds: Sequence[str] = COLLECTIVE_KINDS) -> bool:
    """By opcode, not by name: ``%psum.165 = ... all-reduce(...)`` is one,
    ``%all_to_all.48`` is one because its opcode is ``all-to-all``; the
    ``-start``/``-done`` halves of an asynchronous one count too."""
    op = opcode(name)
    for suffix in ("-start", "-done"):
        if op.endswith(suffix):
            op = op[:-len(suffix)]
    return op in kinds or op.split(".")[0] in kinds


def collective_time(events: Sequence[Event], kinds: Sequence[str]
                    ) -> Tuple[float, float]:
    """(total ns, exposed ns) of the collective operations of ``kinds`` on
    one chip: exposed is the part of their intervals during which no other
    operation runs on that chip."""
    coll = [e for e in events if is_collective(e[0], kinds)]
    other = merged_intervals(e for e in events
                             if not is_collective(e[0]))
    total = busy_ns(coll)
    hidden = sum(overlap_ns(other, a, b) for a, b in merged_intervals(coll))
    return total, total - hidden


def events_inside(events: Sequence[Event], spans: Sequence[Event],
                  span_name: str) -> List[List[Event]]:
    """Group device events by the host span of ``span_name`` they start in:
    one list per span, in order (a call that ends in a host read holds its
    device work inside its own span)."""
    out = []
    evs = sorted(events, key=lambda e: e[1])
    i = 0
    for sp in spans:
        name, s, d = sp[:3]
        if name != span_name:
            continue
        while i < len(evs) and evs[i][1] < s:
            i += 1
        j = i
        while j < len(evs) and evs[j][1] < s + d:
            j += 1
        out.append(evs[i:j])
    return out


def window(trace: dict, name: str = WINDOW_SPAN) -> Optional[Tuple[float, float]]:
    """[start, end] ns of the benchmark's traced window span."""
    for sp in host_spans(trace):
        if sp[0] == name:
            return sp[1], sp[1] + sp[2]
    return None


def busy_per_span(ops: Sequence[Event], spans: Sequence[Event],
                  span_name: str) -> List[Tuple[float, float, dict]]:
    """For each host span of ``span_name``: (device-busy ns of the operations
    that start inside it, the span's own ns, its arguments)."""
    named = [sp for sp in spans if sp[0] == span_name]
    groups = events_inside(ops, spans, span_name)
    return [(busy_ns(g), sp[2], sp[3] if len(sp) > 3 else {})
            for sp, g in zip(named, groups)]


def main_program_runs(modules: Sequence[Event], t0: float, t1: float
                      ) -> float:
    """Runs, inside [t0, t1], of the program with most device time among a
    chip's program events (a trainer's step among its little helpers): the
    time of its events inside the window over the median length of a whole
    one, so a run cut by the window's edge counts as the part it is."""
    total: Dict[str, float] = {}
    for ev in modules:
        total[ev[0]] = total.get(ev[0], 0.0) + ev[2]
    if not total:
        return 0.0
    main = max(total, key=total.get)
    whole = sorted(ev[2] for ev in modules if ev[0] == main)
    inside = busy_ns(clip((ev for ev in modules if ev[0] == main), t0, t1))
    return inside / whole[len(whole) // 2]


def collective_ms_per_run(ops: Sequence[Event], runs: float,
                          kinds: Sequence[str], exposed: bool
                          ) -> Optional[float]:
    """Milliseconds in the collectives of ``kinds`` per run of the main
    program on one chip: all of it, or its exposed part. (``ops`` are the
    chip's operations cut to the window ``runs`` was counted in.)"""
    if not runs:
        return None
    total, bare = collective_time(ops, kinds)
    return (bare if exposed else total) / runs / 1e6
