"""What the PROGRAM wrote into a profiler trace, read back: its own host
spans (``uccl_tpu.obs.span`` -> ``jax.profiler.TraceAnnotation("uccl." +
name)``) and, for every device operation, the ``jax.named_scope`` path it
was traced under. ``trace_reduce.py`` keeps reading the benchmark's own
``chipbench.*`` spans; this file reads ``uccl.*`` and nothing of the
benchmark's, so a program without spans and scopes (the parent of the PR
that added them) gives every reader here ``None``.

Where the scope path lives (libtpu 0.0.34, looked at with
``tools/dump_trace.py`` and a raw parse): NOT in the event's name (the HLO
instruction's text, no metadata) and NOT in the event's own stats, which
are all ``jax.profiler.ProfileData`` shows; it is the ``tf_op`` stat of the
event's METADATA in the device plane — ``jit(uccl_moe_verify_slots)/
moe.experts/ebf,efh->ebh/dot_general:``. A fusion carries its root's path;
an operation the compiler put in itself (a copy of a buffer that was not
donated) carries none. So the device planes are parsed here from the
protobuf wire format directly: a dozen field numbers of ``xplane.proto``
and no dependency. Times are the line's ``timestamp_ns`` plus the event's
``offset_ps``, the clock ``ProfileData`` reports.

The two planes' clocks disagree. On the v5e machines of PR 24 a program's
first operation is stamped 1.3 ms BEFORE the host event in which the
runtime enqueued it. Causality bounds the lead from both sides
(:func:`device_clock_lead`): no program starts before its
``DoEnqueueProgram`` and none is reported by ``CompleteCallbacks`` before
it ended (same ``run_id``). :func:`load` moves the device's operations onto
the host's clock by the middle of that bound, so an operation is found in
the span the host was really in (0 where the bounds cross: a fifth of the
traces; ``step_timeline.lead_from_runtime`` leaves the stray pairs out).

The reductions that depend on a model family's scope names are in
``chipbench/scopes.py``, which takes the family; here are the parser, the
clock, and the reductions over plain lists of operations.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench import trace_reduce as tr
from chipbench.stats import percentile

PREFIX = "uccl."
STEP = PREFIX + "engine.step"
DECODE = PREFIX + "wire.decode"
PREFILL = PREFIX + "wire.prefill"

# the named scopes of the device programs (uccl_tpu/models, ep, ops)
SCOPES = ("embed", "attn.qkv", "attn.kv_write", "attn.core", "attn.out",
          "attn.flash", "moe.router", "moe.route", "moe.dispatch",
          "moe.experts", "moe.combine", "head")
MOE_EXPERTS = ("moe.experts",)
MOE_EXCHANGE = ("moe.router", "moe.route", "moe.dispatch", "moe.combine")
ATTENTION = ("attn.qkv", "attn.kv_write", "attn.core", "attn.out",
             "attn.flash")

Op = Tuple  # (name, start_ns, dur_ns, scope path or "")

_WRAPPED = re.compile(r"^(?:[\w.\-]+\()+|\)+$")


@functools.lru_cache(maxsize=None)  # a program has a few hundred paths
def scope_of(path: str, scopes: Tuple[str, ...] = SCOPES) -> Optional[str]:
    """The innermost known scope among the components of an operation's
    path. A component matches whole, wrappers of a transformation aside:
    ``transpose(jvp(moe.experts))`` and a rematerialised ``checkpoint/
    moe.experts`` are ``moe.experts``; ``not.moe.experts`` is not."""
    for part in reversed(path.rstrip(":").split("/")):
        part = _WRAPPED.sub("", part)
        if part in scopes:
            return part
    return None


# -- the protobuf wire format, as far as xplane.proto needs it ---------------

def _varint(buf, i: int) -> Tuple[int, int]:
    x = buf[i]
    i += 1
    if x < 0x80:
        return x, i
    x &= 0x7F
    shift = 7
    while True:
        y = buf[i]
        i += 1
        x |= (y & 0x7F) << shift
        if y < 0x80:
            return x, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) of one message: an int for a varint, (start,
    end) for a length-delimited field, None for a fixed-width one."""
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif kind == 1:
            val, i = None, i + 8
        elif kind == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"xplane: wire type {kind} at byte {i}")
        yield key >> 3, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """(key, (start, end) of the value message) of a map<int64, Message>."""
    key, val = 0, (span[1], span[1])
    for num, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _device_plane(buf, span):
    """(chip index, its ``XLA Ops`` events as (name, start_ns, dur_ns, scope
    path)) of one XPlane; None for a plane that is not a chip's."""
    name, lines, events, stat_names = "", [], [], {}
    for num, v in _fields(buf, *span):
        if num == 2:
            name = _text(buf, v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            events.append(v)
        elif num == 5:
            key, val = _map_entry(buf, v)
            for n2, v2 in _fields(buf, *val):
                if n2 == 2:
                    stat_names[key] = _text(buf, v2)
    if not re.fullmatch(r"/device:TPU:\d+", name):
        return None
    path_stat = {k for k, n in stat_names.items() if n == "tf_op"}
    meta: Dict[int, Tuple[str, str]] = {}
    for entry in events:
        key, val = _map_entry(buf, entry)
        ev_name, path = "", ""
        for num, v in _fields(buf, *val):
            if num == 2:
                ev_name = _text(buf, v)
            elif num == 5:
                stat = dict(_fields(buf, *v))
                if stat.get(1) in path_stat:
                    if 5 in stat:
                        path = _text(buf, stat[5])
                    elif 7 in stat:  # a reference into the stat names
                        path = stat_names.get(stat[7], "")
        meta[key] = (ev_name, path)
    ops: List[Op] = []
    for span_l in lines:
        line_name, t0_ns, evs = "", 0, []
        for num, v in _fields(buf, *span_l):
            if num == 2:
                line_name = _text(buf, v)
            elif num == 3:
                t0_ns = v
            elif num == 4:
                evs.append(v)
        if line_name != tr.OPS_LINE:
            continue
        for i, end in evs:
            mid = off = dur = 0
            for num, v in _fields(buf, i, end):
                if num == 1:
                    mid = v
                elif num == 2:
                    off = v
                elif num == 3:
                    dur = v
            ev_name, path = meta.get(mid, ("", ""))
            ops.append((ev_name, t0_ns + off / 1000.0, dur / 1000.0, path))
    return int(name.rsplit(":", 1)[1]), sorted(ops, key=lambda e: e[1])


def device_ops(data: bytes) -> List[List[Op]]:
    """Per chip (in the order of ``/device:TPU:<i>``), the operations of its
    ``XLA Ops`` line as (name, start_ns, dur_ns, scope path), by start."""
    buf = memoryview(data)
    chips = [_device_plane(buf, v) for num, v in _fields(buf, 0, len(buf))
             if num == 1]
    return [ops for _, ops in sorted(c for c in chips if c is not None)]


ENQUEUED, COMPLETED = "DoEnqueueProgram", "CompleteCallbacks"


def host_events(path: str):
    """(the program's own annotations on the host threads with their
    arguments, as (name, start_ns, dur_ns, {argument: value}) by start;
    {run_id: ns at which the runtime enqueued that program run}; {run_id: ns
    at which it reported the run complete}; {run_id: (start_ns, end_ns) of
    the run on chip 0})."""
    import jax

    spans, enqueued, completed, ran = [], {}, {}, {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == tr.MODULES_LINE:
                    for ev in line.events:
                        run = dict(ev.stats).get("run_id")
                        if run is not None:
                            ran[run] = (float(ev.start_ns),
                                        float(ev.start_ns + ev.duration_ns))
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append((ev.name, float(ev.start_ns),
                                  float(ev.duration_ns),
                                  {k: v for k, v in ev.stats
                                   if isinstance(v, (int, float, str))}))
                elif ev.name in (ENQUEUED, COMPLETED):
                    run = dict(ev.stats).get("run_id")
                    if run is not None:
                        (enqueued if ev.name == ENQUEUED
                         else completed)[run] = float(ev.start_ns)
    return sorted(spans, key=lambda e: e[1]), enqueued, completed, ran


def device_clock_lead(enqueued: Dict[int, float], completed: Dict[int, float],
                      ran: Dict[int, Tuple[float, float]]
                      ) -> Optional[Tuple[float, float]]:
    """(low, high) ns by which the device plane's clock runs AHEAD of the
    host plane's: every run starts at or after its enqueueing and ends at
    or before its completion is reported, so ``enqueued - start <= lead <=
    completed - end`` for each. None where the runtime's events are not in
    the trace (another libtpu's names) or contradict each other."""
    low = [enqueued[r] - ran[r][0] for r in ran if r in enqueued]
    high = [completed[r] - ran[r][1] for r in ran if r in completed]
    if not low or not high or max(low) > min(high):
        return None
    return max(low), min(high)


class ProgramTrace:
    """One trace file: the program's host spans and each chip's operations
    with their scope paths, the operations moved onto the host's clock by
    ``lead_ns`` (the middle of ``lead_bounds_ns``; 0 where unknown)."""

    def __init__(self, spans: Sequence[tuple], ops: Sequence[Sequence[Op]],
                 lead_bounds_ns: Optional[Tuple[float, float]] = None):
        self.spans = list(spans)
        self.lead_bounds_ns = lead_bounds_ns
        self.lead_ns = sum(lead_bounds_ns) / 2 if lead_bounds_ns else 0.0
        self.ops = [[(n, s + self.lead_ns, d, path) for n, s, d, path in o]
                    for o in ops]


@functools.lru_cache(maxsize=2)
def load(path: str) -> ProgramTrace:
    """Read a trace once, however many readers ask."""
    with open(path, "rb") as f:
        data = f.read()
    spans, enqueued, completed, ran = host_events(path)
    return ProgramTrace(spans, device_ops(data),
                        device_clock_lead(enqueued, completed, ran))


# -- reductions ---------------------------------------------------------------

def spans_in(spans: Iterable[tuple], name: str, t0: float, t1: float
             ) -> List[tuple]:
    """Spans of one name that start inside [t0, t1)."""
    return [sp for sp in spans if sp[0] == name and t0 <= sp[1] < t1]


def by_scope(ops: Sequence[Op], scopes: Tuple[str, ...] = SCOPES
             ) -> Dict[Optional[str], float]:
    """Device-busy ns of ``ops`` by scope of ``scopes`` (None: under none)."""
    by: Dict[Optional[str], list] = {}
    for ev in ops:
        by.setdefault(scope_of(ev[3], scopes), []).append(ev)
    return {s: tr.busy_ns(evs) for s, evs in by.items()}


def busy_by_scope(ops: Sequence[Op], spans: Sequence[tuple], span_name: str,
                  scopes: Tuple[str, ...] = SCOPES
                  ) -> List[Dict[Optional[str], float]]:
    """For each span of ``span_name`` in ``spans``: :func:`by_scope` of the
    operations that start inside it."""
    return [by_scope(group, scopes)
            for group in tr.events_inside(ops, spans, span_name)]


def scope_ms(rows: Sequence[Dict[Optional[str], float]],
             scopes: Sequence[str]) -> Optional[float]:
    """Median over the spans of the device time under ``scopes``, ms; None
    where no span holds an operation under any scope at all."""
    if not any(s is not None for row in rows for s in row):
        return None
    return percentile([sum(row.get(s, 0.0) for s in scopes)
                       for row in rows if row], 50) / 1e6


def unscoped_share(ops: Sequence[Op], scopes: Tuple[str, ...] = SCOPES
                   ) -> Optional[float]:
    """Share (%) of the operations' device-busy time in which NO operation
    under a scope of ``scopes`` ran; None where nothing is scoped (a program
    without scopes). A ``while`` the trace shows under no scope envelops its
    body's operations: the time of the envelope that no scoped body
    operation covers (the loop's own turns) is unscoped, the body's is
    not."""
    scoped = [ev for ev in ops if scope_of(ev[3], scopes) is not None]
    if not scoped:
        return None
    busy = tr.busy_ns(ops)
    return 100.0 * (busy - tr.busy_ns(scoped)) / busy


# -- what chipbench/scopes.py and the timelines read a view's trace through --

def _loaded(view) -> Optional[ProgramTrace]:
    path = view.record.get("trace_path")
    if not path or view.window is None:
        return None
    loaded = load(path)
    return loaded if loaded.spans and loaded.ops else None


@functools.lru_cache(maxsize=2)
def _window_ops(path: str, t0: float, t1: float) -> List[Op]:
    return tr.clip(load(path).ops[0], t0, t1)
