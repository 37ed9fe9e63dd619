"""A decode call laid out on one clock, cut at the DEVICE's events: when its
``uccl.backend.launch`` opened, when the first operation of the step's own
program ran, when its last one ended, when ``uccl.backend.fetch`` closed,
and which program runs the chip started inside the call's
``uccl.wire.decode``. From those, per call:

* dispatch latency — launch opens -> the step's program starts: Python, the
  trivial programs dispatched before the step's, the runtime's enqueue;
* completion latency — the step's program ends -> fetch closes: the trivial
  programs after it and every device-to-host read;
* between calls — this call's fetch closes -> the next step's launch opens
  (``step`` n, n + 1 on the spans, neither step with a ``uccl.wire.prefill``):
  retire, the harness's loop, admit, the next call's arrays and stage.

The three and the program's own span add up to the decode-only period by
construction, and none is cut at the boundary between two host spans, which
races the device (the thread leaves ``backend.launch`` when the asynchronous
dispatch returns, before or after the chip starts).

The device plane's clock runs ahead of the host plane's by a lead that is
one number a profiler session (0.5 or 1.3 ms on the v5e machines of PR 40,
flat over a 51 s window). Causality bounds it (``program_trace.
device_clock_lead``: no run starts before its ``DoEnqueueProgram`` and none
is reported by ``CompleteCallbacks`` before it ended), and on those machines
the two bounds lie 0.01-0.2 ms apart: taken as the largest and the smallest
over 20,000 runs they cross by a few microseconds of jitter in a fifth of the
traces, ``ProgramTrace.lead_bounds_ns`` is then ``None`` and ``load`` applies
a lead of 0. So the lead here is the middle of the SAME events' bounds taken
at the 99th and the 1st percentile (:func:`lead_from_runtime`), which a few
stray pairs do not move: an estimate that leaves a hundredth of the
constraints out, no longer a strict bound. Only a trace WITHOUT the
runtime's events (another libtpu's names) is bounded the same way from the
calls themselves (each step's program starts after its launch opened and
ends before its fetch closed: as wide as the least dispatch plus the least
completion, 2 ms on the chip, so its middle splits the two a quarter of a
millisecond less well). Where the bounds of the one source a trace has
contradict each other there is no lead: dispatch, completion and everything
that places a device event among host events read ``None`` — never a lead
of 0, and never the other source's — while the time between calls, host
times alone, still reads. Which source a run's lead came from is printed in
its log as the timeline is made (:func:`of`). An error in the lead moves
dispatch and completion by the same amount in opposite directions and leaves
their sum alone.

Times are ns. A program run is an event of the chip's ``XLA Modules`` line:
it opens with the run's first operation on the ``XLA Ops`` line and closes
with its last (to 0.3 us on the recorded v5e trace,
``chipbench/tests/fixtures/chat.trace.json``)."""

from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from chipbench import program_trace as pt
from chipbench import trace_reduce as tr
from chipbench.stats import percentile

LAUNCH = pt.PREFIX + "backend.launch"
FETCH = pt.PREFIX + "backend.fetch"
STEP_PROGRAM = re.compile(r"jit_uccl_\w+_(?:verify|decode)_slots\b")
PREFILL_PROGRAM = re.compile(r"jit_uccl_\w+_prefill_slots\b")

Run = Tuple  # (program name, first operation starts, last operation ends)


class Call(NamedTuple):
    """One ``uccl.wire.decode`` span. Host times as the trace has them;
    ``runs`` (the program runs that start inside the span) and ``own`` (the
    step's program among them) already moved onto the host's clock."""
    step: Optional[int]  # the span's ``step`` argument; None: it has none
    n: int  # its ``n``: the slots decoding, one token gap each
    launch: float  # its backend.launch opens
    fetched: float  # its backend.fetch closes
    runs: List[Run]
    own: Optional[Run]


def program_runs(modules: Sequence[tuple]) -> List[Run]:
    """The events of the ``XLA Modules`` line as runs, by start."""
    return sorted(((m[0], m[1], m[1] + m[2]) for m in modules),
                  key=lambda r: r[1])


def _own(runs: Sequence[Run]) -> Optional[Run]:
    """The step's program among a call's runs: the one named for it, else
    the longest."""
    named = [r for r in runs if STEP_PROGRAM.match(r[0])]
    if named:
        return named[0]
    return max(runs, key=lambda r: r[2] - r[1]) if runs else None


def _wire_calls(spans: Sequence[tuple], t0: float, t1: float):
    """(wire.decode span, launch opens, fetch closes) of each decode call
    that starts in [t0, t1) and holds both backend spans."""
    inner = [sp for sp in spans if sp[0] in (LAUNCH, FETCH)]
    out, k = [], 0
    for wire in pt.spans_in(spans, pt.DECODE, t0, t1):
        start, end = wire[1], wire[1] + wire[2]
        while k < len(inner) and inner[k][1] < start:
            k += 1
        launch = fetched = None
        j = k
        while j < len(inner) and inner[j][1] < end:
            if inner[j][0] == LAUNCH and launch is None:
                launch = inner[j][1]
            elif inner[j][0] == FETCH:
                fetched = inner[j][1] + inner[j][2]
            j += 1
        if launch is not None and fetched is not None:
            out.append((wire, launch, fetched))
    return out


def _bounds(lows: Sequence[float], highs: Sequence[float]
            ) -> Optional[Tuple[float, float]]:
    """(low, high) of a lead that is at least each of ``lows`` and at most
    each of ``highs``, a hundredth of each left out; None where either is
    empty or what is left still contradicts."""
    if not lows or not highs:
        return None
    low, high = percentile(lows, 99), percentile(highs, 1)
    return (low, high) if low <= high else None


def lead_from_runtime(enqueued: Dict[int, float], completed: Dict[int, float],
                      ran: Dict[int, Tuple[float, float]]
                      ) -> Optional[Tuple[float, float]]:
    """(low, high) ns of the device clock's lead from the runtime's own
    events, as ``program_trace.host_events`` returns them: ``enqueued -
    start <= lead <= completed - end`` for every run."""
    return _bounds([enqueued[r] - ran[r][0] for r in ran if r in enqueued],
                   [completed[r] - ran[r][1] for r in ran if r in completed])


def lead_from_calls(spans: Sequence[tuple], runs: Sequence[Run], t0: float,
                    t1: float) -> Optional[Tuple[float, float]]:
    """(low, high) ns of the device clock's lead from the calls alone: the
    step's program (the run that shares most time with the call as the
    trace stamps both; a lead is a fraction of a call) starts at or after
    its launch opened and ends at or before its fetch closed."""
    low, high, i = [], [], 0
    for _, launch, fetched in _wire_calls(spans, t0, t1):
        while i < len(runs) and runs[i][2] <= launch:
            i += 1
        near, j = [], i
        while j < len(runs) and runs[j][1] < fetched:
            near.append(runs[j])
            j += 1
        named = [r for r in near if STEP_PROGRAM.match(r[0])] or near
        if not named:
            continue
        own = max(named, key=lambda r: min(r[2], fetched) - max(r[1], launch))
        low.append(launch - own[1])
        high.append(fetched - own[2])
    return _bounds(low, high)


def decode_calls(spans: Sequence[tuple], runs: Sequence[Run], t0: float,
                 t1: float) -> List[Call]:
    """The window's decode calls, in order; ``runs`` on the host's clock."""
    calls = _wire_calls(spans, t0, t1)
    inside = tr.events_inside(runs, [wire for wire, _, _ in calls], pt.DECODE)
    out = []
    for (wire, launch, fetched), mine in zip(calls, inside):
        args = wire[3] if len(wire) > 3 else {}
        step = args.get("step")
        out.append(Call(None if step is None else int(step),
                        int(args.get("n", 1)), launch, fetched, mine,
                        _own(mine)))
    return out


def steps_with_prefill(spans: Sequence[tuple]) -> set:
    return {int(sp[3]["step"]) for sp in spans
            if sp[0] == pt.PREFILL and len(sp) > 3 and "step" in sp[3]}


def back_to_back(calls: Sequence[Call], prefill_steps: set
                 ) -> List[Tuple[Call, Call]]:
    """The pairs of decode calls of consecutive steps (``step`` n, n + 1)
    neither of which ran a prefill: a decode-only period each."""
    return [(a, b) for a, b in zip(calls, calls[1:])
            if a.step is not None and b.step == a.step + 1
            and not {a.step, b.step} & prefill_steps]


def between_calls_ns(calls: Sequence[Call], prefill_steps: set) -> List[float]:
    """Fetch closes -> the next step's launch opens, over
    :func:`back_to_back`."""
    return [b.launch - a.fetched
            for a, b in back_to_back(calls, prefill_steps)]


class Timeline(NamedTuple):
    """What the readers share: the window's decode calls; every program run
    of the trace and the intervals in which the chip ran an operation, on
    the host's clock; where the lead's bounds came from ("runtime": the
    trace's own events; "calls": a trace without them) and the bounds, ns.
    Where they contradict each other ``lead_bounds``, ``runs`` and ``busy``
    are None and the calls hold no runs: only host times read."""
    calls: List[Call]
    runs: Optional[List[Run]]
    busy: Optional[List[Tuple[float, float]]]
    prefill_steps: set
    lead_from: str
    lead_bounds: Optional[Tuple[float, float]]


def build(spans: Sequence[tuple], modules: Sequence[tuple],
          ops: Sequence[tuple], runtime: Tuple[dict, dict, dict], t0: float,
          t1: float) -> Timeline:
    """The timeline of one trace and window. ``modules`` and ``ops`` are
    chip 0's ``XLA Modules`` and ``XLA Ops`` lines on the device's clock,
    ``runtime`` the three dictionaries of :func:`lead_from_runtime` (empty
    where the trace has no such events: the one case the calls bound the
    lead in)."""
    runs = program_runs(modules)
    if runtime[0] or runtime[1]:
        bounds, source = lead_from_runtime(*runtime), "runtime"
    else:
        bounds, source = lead_from_calls(spans, runs, t0, t1), "calls"
    prefills = steps_with_prefill(spans)
    if bounds is None:
        return Timeline(decode_calls(spans, [], t0, t1), None, None,
                        prefills, source, None)
    lead = sum(bounds) / 2
    runs = [(n, s + lead, e + lead) for n, s, e in runs]
    busy = [(a + lead, b + lead) for a, b in tr.merged_intervals(ops)]
    return Timeline(decode_calls(spans, runs, t0, t1), runs, busy, prefills,
                    source, bounds)


_BUILT: Dict[tuple, Optional[Timeline]] = {}  # the last trace's: 28 readers


def of(view) -> Optional[Timeline]:
    """The timeline of a traced run's view, made once, with the source and
    the bounds of its lead printed into the run's log beside the readings
    they place; None without a trace or without the program's spans."""
    if pt._loaded(view) is None:
        return None
    path = view.record["trace_path"]
    key = (path,) + tuple(view.window)
    if key not in _BUILT:
        chip = tr.device_planes(view.trace)[0]
        spans, *runtime = pt.host_events(path)
        _BUILT.clear()
        t = _BUILT[key] = build(
            spans, tr.line_events(chip, tr.MODULES_LINE),
            tr.line_events(chip, tr.OPS_LINE), runtime, *view.window)
        print("chipbench: step_timeline lead_from=%s lead_bounds_ms=%s" % (
            t.lead_from, t.lead_bounds
            and [round(b / 1e6, 6) for b in t.lead_bounds]), flush=True)
    return _BUILT[key]


def _median_ms(view, values_of) -> Optional[float]:
    """A reader's body: the median, in ms, of what ``values_of`` takes (ns)
    from the view's timeline; None where there is none."""
    t = of(view)
    values = values_of(t) if t is not None else ()
    return percentile(values, 50) / 1e6 if values else None


# -- what the per-layer readers call -----------------------------------------

def decode_dispatch_latency_ms(view) -> Optional[float]:
    """Median over the window's decode calls of (first operation of the
    step's program) - (``backend.launch`` opens)."""
    return _median_ms(view, lambda t: [c.own[1] - c.launch
                                       for c in t.calls if c.own])


def decode_completion_latency_ms(view) -> Optional[float]:
    """Median of (``backend.fetch`` closes) - (last operation of the step's
    program)."""
    return _median_ms(view, lambda t: [c.fetched - c.own[2]
                                       for c in t.calls if c.own])


def host_between_calls_ms(view) -> Optional[float]:
    """Median of :func:`between_calls_ns`; None on a program whose spans
    carry no ``step``."""
    return _median_ms(view,
                      lambda t: between_calls_ns(t.calls, t.prefill_steps))


def device_programs_per_decode_call(view) -> Optional[float]:
    """Median count of program runs that start inside a decode call."""
    t = of(view)
    if t is None or t.runs is None or not t.calls:
        return None
    return percentile([len(c.runs) for c in t.calls], 50)
