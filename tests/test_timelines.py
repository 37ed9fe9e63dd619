"""The benchmark's two timeline readers on hand-made events (ISSUE 40):
``chipbench/step_timeline.py`` (a decode call cut at the device's events)
and ``chipbench/request_timeline.py`` (a first-token wait by what the device
did in it). Times below are ms on the HOST's clock; the device's events are
stamped ``LEAD`` earlier, as the chip's plane stamps them."""

import json
import os

import pytest

from chipbench import program_trace as pt
from chipbench import request_timeline as rt
from chipbench import run as R
from chipbench import step_timeline as st

MS = 1_000_000  # ns
LEAD = 1.0  # the device's clock runs this far ahead, ms
STEP = "jit_uccl_moe_verify_slots(7)"
PREFILL = "jit_uccl_moe_prefill_slots(8)"
WINDOW = (0.0, 200.0 * MS)


def _call(t, step):
    """A decode call opening at ``t``: stage [t, t+1), launch [t+1, t+2),
    fetch [t+2, t+9.5), inside wire.decode [t, t+10). The thread has LEFT
    backend.launch (t+2) when the step's program starts (t+2.6)."""
    args = {"n": 1, "kv_rows": 10}
    if step is not None:
        args["step"] = step
    return [("uccl.wire.decode", t, 10, args),
            ("uccl.backend.stage", t, 1, {}),
            ("uccl.backend.launch", t + 1, 1, {}),
            ("uccl.backend.fetch", t + 2, 7.5, {})]


def _device(t):
    """The program runs of the call at ``t`` (host time): a trivial program,
    the step's [t+2.6, t+7.4), two trivial ones after it."""
    return [("jit_broadcast_in_dim(1)", t + 1.5, 0.1),
            (STEP, t + 2.6, 4.8),
            ("jit_squeeze(2)", t + 7.6, 0.1),
            ("jit_reshape(3)", t + 7.8, 0.1)]


def _ns(events, shift=0.0):
    return [(e[0], (e[1] - shift) * MS, e[2] * MS) + tuple(e[3:])
            for e in events]


def _trace(steps, prefill_step=3, marks=True):
    """Decode calls at 10, 21, 51, 62, 90 ms with the given ``step``
    arguments, a prefill call of ``prefill_step`` at [32, 50), and one
    request admitted at 5 ms whose first token comes at 45."""
    spans, modules = [], []
    for t, step in zip((10, 21, 51, 62, 90), steps):
        spans += _call(t, step)
        modules += _device(t)
    args = {"n": 1, "chunk": 8}
    if prefill_step is not None:
        args["step"] = prefill_step
    spans += [("uccl.wire.prefill", 32, 18, args),
              ("uccl.backend.launch", 33, 1, {}),
              ("uccl.backend.fetch", 34, 15, {})]
    modules.append((PREFILL, 35, 10.0))
    if marks:
        spans += [("uccl.admit", 5, 0, {"rid": 1, "slot": 0}),
                  ("uccl.first_token", 45, 0, {"rid": 1, "ttft_ms": 40.0}),
                  # admitted in the window, first token after it closed
                  ("uccl.admit", 150, 0, {"rid": 2, "slot": 1}),
                  ("uccl.first_token", 210, 0, {"rid": 2}),
                  # admitted before the window opened
                  ("uccl.admit", -5, 0, {"rid": 3, "slot": 2}),
                  ("uccl.first_token", 20, 0, {"rid": 3})]
    key = lambda e: e[1]
    return sorted(_ns(spans), key=key), sorted(_ns(modules, LEAD), key=key)


STEPS = (1, 2, 3, 4, 6)
NO_RUNTIME = ({}, {}, {})  # a trace without the runtime's events


def _ops(modules):
    """The chip's ``XLA Ops`` line under ``modules``: each run one operation
    from its start to its end (the prefill program's is cut in
    :func:`test_reader`'s trace, below)."""
    return [("%fusion = f32[8] fusion()", m[1], m[2], "") for m in modules]


def _build(spans, modules, runtime):
    return st.build(spans, modules, _ops(modules), runtime, *WINDOW)


def _runtime(modules, slack=0.2, stray=0):
    """The runtime's events of ``modules`` (on the device's clock), as
    ``program_trace.host_events`` hands them: each run enqueued ``slack`` ms
    before it starts and reported complete ``slack`` ms after it ends, on
    the host's clock — so the lead is LEAD +- slack. ``stray`` pairs are
    stamped as no lead allows (enqueued after the start)."""
    ran = {i: (m[1], m[1] + m[2]) for i, m in enumerate(modules)}
    enq = {i: s + (LEAD - slack) * MS for i, (s, _) in ran.items()}
    comp = {i: e + (LEAD + slack) * MS for i, (_, e) in ran.items()}
    for i in range(stray):
        enq[i] += 5 * MS
    return enq, comp, ran


def test_a_run_spans_its_first_to_last_operation_on_a_recorded_trace():
    """What lets the readers take a run's bounds off the ``XLA Modules``
    line: on a trace recorded on the chip (the benchmark's fixture) every
    such event opens with its first operation and closes with its last, to
    under a microsecond."""
    from chipbench import trace_reduce as tr

    with open(os.path.join(R.HERE, "tests", "fixtures",
                           "chat.trace.json")) as f:
        chip = tr.device_planes(json.load(f))[0]
    ops = tr.line_events(chip, tr.OPS_LINE)
    runs = st.program_runs(tr.line_events(chip, tr.MODULES_LINE))
    assert len(runs) > 30 and runs == sorted(runs, key=lambda r: r[1])
    i = 0
    for name, start, end in runs[:-1]:  # the fixture's operations are cut
        while ops[i][1] < start:        # short inside its last program
            i += 1
        first, last = ops[i][1], 0.0
        while i < len(ops) and ops[i][1] < end:
            last = max(last, ops[i][1] + ops[i][2])
            i += 1
        assert 0 <= first - start < 1000 and abs(end - last) < 1000, name


def test_a_call_is_cut_at_the_device_events_not_at_the_host_boundary():
    spans, modules = _trace(STEPS)
    t = _build(spans, modules, _runtime(modules))
    assert t.lead_from == "runtime" and len(t.calls) == 5
    assert t.lead_bounds == pytest.approx((0.8 * MS, 1.2 * MS))
    first = t.calls[0]
    assert first.step == 1 and len(first.runs) == 4
    assert first.own[0] == STEP
    # the program starts 0.6 ms after backend.launch CLOSED: dispatch is
    # still counted from where the launch opened
    assert first.own[1] - first.launch == pytest.approx(1.6 * MS)
    assert first.fetched - first.own[2] == pytest.approx(2.1 * MS)
    # consecutive steps without a prefill: (1, 2) alone — 3 ran a prefill,
    # so (2, 3) and (3, 4) are out, and 4 -> 6 are not back to back
    assert t.prefill_steps == {3}
    assert st.between_calls_ns(t.calls, t.prefill_steps) \
        == pytest.approx([2.5 * MS])


def test_the_longest_run_is_the_steps_program_where_none_is_named_for_it():
    spans, modules = _trace(STEPS)
    renamed = [("jit_f(1)" if m[0] == STEP else m[0],) + m[1:]
               for m in modules]
    t = _build(spans, renamed, _runtime(renamed))
    assert t.calls[0].own[0] == "jit_f(1)"
    assert t.calls[0].own[1] - t.calls[0].launch == pytest.approx(1.6 * MS)


def test_the_runtimes_bounds_hold_a_few_stray_pairs():
    """On the chip the two bounds lie 10-200 us apart over 20,000 runs and
    the strict ones (the largest lower, the smallest upper) cross in a fifth
    of the traces; a hundredth of each side is left out here."""
    _, modules = _trace(STEPS)
    many = [(n, s + k * 300 * MS, d) for k in range(20) for n, s, d in modules]
    assert st.lead_from_runtime(*_runtime(many)) \
        == pytest.approx((0.8 * MS, 1.2 * MS))
    enq, comp, ran = _runtime(many, stray=3)  # 3 of 420: enqueued "late"
    assert pt.device_clock_lead(enq, comp, ran) is None  # the strict bounds
    assert st.lead_from_runtime(enq, comp, ran) \
        == pytest.approx((0.8 * MS, 1.2 * MS))
    # one pair in five is no stray: the bounds contradict, and say so
    assert st.lead_from_runtime(*_runtime(modules[:5], stray=1)) is None
    assert st.lead_from_runtime({}, {}, {}) is None


def test_without_the_runtimes_events_the_calls_bound_the_lead():
    spans, modules = _trace(STEPS)
    # every call: launch - start = 11 - (12.6 - 1) and fetch - end = 19.5 -
    # (17.4 - 1), the same in each
    assert st.lead_from_calls(spans, st.program_runs(modules),
                              *WINDOW) == pytest.approx((-0.6 * MS,
                                                         3.1 * MS))
    t = _build(spans, modules, NO_RUNTIME)
    assert t.lead_from == "calls"
    c = t.calls[0]
    # the middle of that bound splits the two evenly; their sum is the
    # true one whatever the lead, and so is the time between calls
    assert (c.own[1] - c.launch) + (c.fetched - c.own[2]) \
        == pytest.approx(3.7 * MS)
    assert st.between_calls_ns(t.calls, t.prefill_steps) \
        == pytest.approx([2.5 * MS])


def _no_device_reading(t):
    """No lead: nothing that places a device event among host events."""
    return t.lead_bounds is None and t.runs is None and t.busy is None \
        and all(c.own is None and c.runs == [] for c in t.calls)


def test_contradicting_bounds_give_no_lead_and_only_host_times_read():
    spans, modules = _trace(STEPS)
    # the call at 62 ms: its program stamped 6 ms longer, past the close of
    # its fetch by more than any call's program started after its launch
    modules = [(n, s, d + 6 * MS if n == STEP and 60 * MS < s < 70 * MS
                else d) for n, s, d in modules]
    assert st.lead_from_calls(spans, st.program_runs(modules),
                              *WINDOW) is None
    t = _build(spans, modules, NO_RUNTIME)
    assert t.lead_from == "calls" and _no_device_reading(t)
    # the time between calls is host times alone, and still reads
    assert st.between_calls_ns(t.calls, t.prefill_steps) \
        == pytest.approx([2.5 * MS])
    # the runtime's bounds, where the trace has them, still hold
    assert _build(spans, modules, _runtime(modules)).lead_bounds is not None


def test_the_calls_never_stand_in_for_runtime_events_that_contradict():
    """One trace, one source: the calls' bounds are 2 ms wide on the chip
    and their middle splits dispatch from completion a quarter of a
    millisecond off, so a reading must not change its definition from run
    to run unseen. The runtime's events there but contradicting: no lead,
    though the calls alone would have given one."""
    spans, modules = _trace(STEPS)
    crossed = _runtime(modules, stray=5)  # 5 of 21 pairs: no stray
    assert st.lead_from_runtime(*crossed) is None
    assert st.lead_from_calls(spans, st.program_runs(modules),
                              *WINDOW) is not None
    t = _build(spans, modules, crossed)
    assert t.lead_from == "runtime" and _no_device_reading(t)
    assert st.between_calls_ns(t.calls, t.prefill_steps) \
        == pytest.approx([2.5 * MS])


def test_a_program_without_step_arguments_has_no_time_between_calls():
    spans, modules = _trace((None,) * 5, prefill_step=None)
    t = _build(spans, modules, _runtime(modules))
    assert [c.step for c in t.calls] == [None] * 5
    assert st.between_calls_ns(t.calls, t.prefill_steps) == []
    assert t.calls[0].own[1] - t.calls[0].launch == pytest.approx(1.6 * MS)


def test_a_wait_is_paired_by_rid_inside_the_window():
    spans, _ = _trace(STEPS)
    assert rt.first_token_waits(spans, *WINDOW) == [(1, 5 * MS, 45 * MS)]
    # a wider window takes the second request in; the third's admit is
    # before any window that starts at 0
    assert [w[0] for w in rt.first_token_waits(spans, 0.0, 300 * MS)] \
        == [1, 2]


RUNS = [(PREFILL, 10, 20), (STEP, 22, 27), ("jit_reshape(3)", 30, 31),
        (PREFILL, 33, 43), (STEP, 44, 48), (STEP, 60, 65)]


def test_a_waits_shares_add_up_where_the_two_lines_agree():
    busy = [(r[1], r[2]) for r in RUNS]  # an operation from first to last
    shares = rt.wait_shares([(1, 5, 45)], RUNS, busy)
    assert shares == pytest.approx({"prefill": 50.0, "decode": 15.0,
                                    "other": 2.5, "idle": 32.5,
                                    "residual": 0.0}, abs=1e-9)
    # two waits: the intervals are summed, then shared
    both = rt.wait_shares([(1, 5, 45), (2, 58, 68)], RUNS, busy)
    assert both["decode"] == pytest.approx(100.0 * (6 + 5) / 50)
    assert both["idle"] == pytest.approx(100.0 * (13 + 5) / 50)
    assert rt.wait_shares([], RUNS, busy) is None


def test_idle_is_walked_on_the_operations_line_and_the_rest_is_residual():
    """Idle time is the gaps between the chip's operations, not what the
    program runs leave of the wait: the four shares are then a check on
    each other, and what they miss of 100 is reported."""
    wait = [(1, 5, 45)]
    # the first prefill run holds a 2 ms gap between its operations: the
    # run's time and idle time both count it
    gap = [(10, 14), (16, 20), (22, 27), (30, 31), (33, 43), (44, 48)]
    shares = rt.wait_shares(wait, RUNS, gap)
    assert shares["prefill"] == pytest.approx(50.0)
    assert shares["idle"] == pytest.approx(100.0 * 15 / 40)
    assert shares["residual"] == pytest.approx(-100.0 * 2 / 40)
    # an operation outside every program run: neither counts it
    stray = [(r[1], r[2]) for r in RUNS[:2]] + [(28, 29.5)] \
        + [(r[1], r[2]) for r in RUNS[2:]]
    shares = rt.wait_shares(wait, RUNS, stray)
    assert shares["idle"] == pytest.approx(100.0 * 11.5 / 40)
    assert shares["residual"] == pytest.approx(100.0 * 1.5 / 40)
    # an operation that straddles the wait's edges is cut to it
    edge = [(0, 20), (22, 27), (30, 31), (33, 43), (44, 50)]
    assert rt.wait_shares(wait, RUNS, edge)["idle"] \
        == pytest.approx(100.0 * (2 + 3 + 2 + 1) / 40)


# -- the 28 readers over one hand-made trace ----------------------------------

class _View:
    """What ``chipbench.run.TraceView`` hands a reader, as far as these
    readers look: the record's trace path, the window, the neutral trace."""

    def __init__(self, modules, ops, path):
        from chipbench import trace_reduce as tr

        self.tr = tr
        self.record = {"trace_path": path}
        self.window = WINDOW
        self.trace = {"planes": [{"name": "/device:TPU:0", "lines": [
            {"name": tr.MODULES_LINE, "events": [list(m) for m in modules]},
            {"name": tr.OPS_LINE, "events": [list(o) for o in ops]}]}]}


def _view(monkeypatch, path, runtime=_runtime, **kw):
    spans, modules = _trace(kw.pop("steps", STEPS), **kw)
    # the prefill program [35, 45) in two operations with 1 ms between
    # them: idle on the operations line, inside the run on the modules line
    ops = [o for m, o in zip(modules, _ops(modules)) if m[0] != PREFILL] \
        + [("%a = f32[8] fusion()", (35 - LEAD) * MS, 4 * MS, ""),
           ("%b = f32[8] fusion()", (40 - LEAD) * MS, 5 * MS, "")]
    # (``ProgramTrace.ops`` only has to be there: these readers take the
    # chip's lines from the view; and the strict bounds are not what they
    # use: None, as in a fifth of the chip's traces)
    loaded = pt.ProgramTrace(spans, [[("%op = x", 0.0, 1.0, "")]], None)
    monkeypatch.setattr(pt, "load", lambda p: loaded)
    monkeypatch.setattr(pt, "host_events",
                        lambda p: (spans,) + runtime(modules))
    return _View(modules, sorted(ops, key=lambda o: o[1]), path)


# the wait [5, 45): the prefill program [35, 45) with 1 ms between its two
# operations, the decode programs [12.6, 17.4) + [23.6, 28.4), trivial
# programs 3 x 0.1 a call, two calls
EXPECTED = {
    "decode_dispatch_latency_ms": 1.6,
    "decode_completion_latency_ms": 2.1,
    "host_between_calls_ms": 2.5,
    "device_programs_per_decode_call": 4,
    "ttft_prefill_dev_share": 100.0 * 10 / 40,
    "ttft_decode_dev_share": 100.0 * 9.6 / 40,
    "ttft_idle_share": 100.0 * (40 - 9 - 9.6 - 0.6) / 40,
}
HOST_TIMES_ALONE = ("host_between_calls_ms",)
NEEDS_THIS_PR = ("host_between_calls_ms", "ttft_prefill_dev_share",
                 "ttft_decode_dev_share", "ttft_idle_share")


def _base(name):
    """A per-layer entry's reading: its name, less the cell's suffix where
    the entry is one cell's copy (``decode_dispatch_latency_ms.chat``)."""
    return name if name in EXPECTED else name.rsplit(".", 1)[0]


def _new_readings():
    """``[(entry, cell)]``: each of this file's readings with each cell an
    entry of it lists, whatever form ``BENCHMARK.json`` has — 28 one-cell
    entries give the same pairs as seven entries that list four cells."""
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return [(m, cell) for m in bench["per_layer"]
            if _base(m["name"]) in EXPECTED for cell in m["workloads"]]


def _pair_id(pair):
    return f"{_base(pair[0]['name'])}@{pair[1]}"


def test_each_new_metric_is_in_all_four_cells():
    pairs = _new_readings()
    cells = {w["name"] for w in json.load(open(os.path.join(
        R.ROOT, "BENCHMARK.json")))["workloads"]}
    assert len(pairs) >= 7 * 4
    assert len({_pair_id(p) for p in pairs}) == len(pairs)  # none twice
    for m, cell in pairs:
        assert m["layer"] == "serving engine"
        assert m["source"] == "program_span" and cell in cells
    # every reading in the same cells: the four the benchmark had when
    # these readings were added, or more of those it has now (a later cell
    # has what the benchmark's cap of 128 per-layer metrics left room for:
    # PERF.md section 3)
    listed = {cell for _, cell in pairs}
    assert len(listed) >= 4
    for name in EXPECTED:
        assert {cell for m, cell in pairs
                if _base(m["name"]) == name} == listed


@pytest.mark.parametrize("pair", _new_readings(), ids=_pair_id)
def test_reader(monkeypatch, pair):
    metric = pair[0]["name"]  # the reader is found by the name the entry has
    base = _base(metric)
    read = R.load_reader(metric).read
    assert read(_view(monkeypatch, "a:" + metric)) \
        == pytest.approx(EXPECTED[base])
    # the parent's program: no ``step`` on its spans, no marks
    parent = read(_view(monkeypatch, "b:" + metric, steps=(None,) * 5,
                        prefill_step=None, marks=False))
    if base in NEEDS_THIS_PR:
        assert parent is None
    else:
        assert parent == pytest.approx(EXPECTED[base])

    # the runtime's events there, their bounds crossed: no lead, and only
    # what needs none reads (the calls' bounds do not stand in)
    crossed = read(_view(monkeypatch, "c:" + metric,
                         runtime=lambda m: _runtime(m, stray=5)))
    if base in HOST_TIMES_ALONE:
        assert crossed == pytest.approx(EXPECTED[base])
    else:
        assert crossed is None

    class NoTrace:  # a traced run of a program without spans
        record = {"trace_path": None}
        window = None

    assert read(NoTrace) is None


def test_the_leads_source_is_in_the_runs_log(monkeypatch, capsys):
    read = R.load_reader(next(
        m["name"] for m, _ in _new_readings()
        if _base(m["name"]) == "decode_dispatch_latency_ms")).read
    read(_view(monkeypatch, "log:runtime"))
    assert "chipbench: step_timeline lead_from=runtime " \
        "lead_bounds_ms=[0.8, 1.2]" in capsys.readouterr().out
    read(_view(monkeypatch, "log:calls", runtime=lambda m: NO_RUNTIME))
    assert "lead_from=calls lead_bounds_ms=[-0.6, 3.1]" \
        in capsys.readouterr().out
    read(_view(monkeypatch, "log:none",
               runtime=lambda m: _runtime(m, stray=5)))
    assert "lead_from=runtime lead_bounds_ms=None" in capsys.readouterr().out


# -- a chunk step whose two calls are launched before either is read (ISSUE 47)

DECODE_PROGRAM = "jit_uccl_moe_decode_slots(9)"
PRE_PATH = "jit(uccl_moe_prefill_slots)/moe.experts/dot_general:"
DEC_PATH = "jit(uccl_moe_decode_slots)/attn.core/dot_general:"
# the chunk step's two programs on the HOST's clock, operation by operation:
# the prefill program [103.6, 119.6), the decode program behind it
PRE_OPS = [(f"%p{i} = f32[8] fusion()", 103.6 + 4 * i, 4.0, PRE_PATH)
           for i in range(4)]


def _dec_ops(t):
    """The decode program's three operations from ``t``: 7 ms."""
    return [("%d0 = f32[8] fusion()", t, 1.0, DEC_PATH),
            ("%d1 = f32[8] fusion()", t + 1.0, 2.3, DEC_PATH),
            ("%d2 = f32[8] fusion()", t + 3.3, 3.7, DEC_PATH)]


def _chunk_step(shape):
    """One chunk step (``step`` 3) between decode-only steps 1, 2 (calls at
    10, 21) and 4, 5 (calls at 150, 161), as ``parent`` lays it out — the
    prefill call staged, launched and read, then the decode call — or as
    the ``change`` does: both launched inside ``wire.prefill``, which closes
    after the prefill call's fetch; ``wire.decode`` holds the decode call's
    fetch alone, and the step's span says ``calls`` = ``together``. Returns
    (spans, modules, operations), the device's events on the host's clock."""
    spans, modules, ops = [], [], []
    for t, step in ((10, 1), (21, 2), (150, 4), (161, 5)):
        spans += _call(t, step)
        spans.append(("uccl.engine.step", t - 0.5, 11, {"decoding": 1}))
        modules += _device(t)
    pre = {"step": 3, "n": 1, "chunk": 8, "rows": 1, "tokens": 8}
    dec = {"step": 3, "n": 1, "kv_rows": 10}
    modules.append((PREFILL, 103.6, 16.0))
    if shape == "parent":
        spans += [("uccl.engine.step", 100, 40, {"decoding": 1}),
                  ("uccl.wire.prefill", 101, 20, pre),
                  ("uccl.backend.stage", 101, 1, {}),
                  ("uccl.backend.launch", 102, 1, {}),
                  ("uccl.backend.fetch", 103, 17.5, {}),
                  ("uccl.wire.decode", 122, 11, dec),
                  ("uccl.backend.stage", 122, 1, {}),
                  ("uccl.backend.launch", 123, 1, {}),
                  ("uccl.backend.fetch", 124, 8.5, {})]
        dec_at = 124.6
    else:
        spans += [("uccl.engine.step", 100, 35,
                   {"decoding": 1, "calls": "together"}),
                  ("uccl.wire.prefill", 101, 20, pre),
                  ("uccl.backend.stage", 101, 1, {}),
                  ("uccl.backend.launch", 102, 1, {}),
                  ("uccl.backend.stage", 103, 1, {}),
                  ("uccl.backend.launch", 104, 1, {}),
                  ("uccl.backend.fetch", 105, 15.5, {}),
                  ("uccl.wire.decode", 122, 6, dec),
                  ("uccl.backend.fetch", 122, 5.5, {})]
        dec_at = 119.7  # behind the prefill program, with no host between
    modules.append((DECODE_PROGRAM, dec_at, 7.0))
    ops = [(m[0], m[1], m[2], "") for m in modules
           if m[0] not in (PREFILL, DECODE_PROGRAM)] \
        + PRE_OPS + _dec_ops(dec_at)
    key = lambda e: e[1]
    return (sorted(_ns(spans), key=key), sorted(_ns(modules), key=key),
            sorted(_ns(ops), key=key))


def _chunk_view(monkeypatch, shape, path):
    spans, modules, ops = _chunk_step(shape)
    device = lambda evs: [(e[0], e[1] - LEAD * MS) + tuple(e[2:])
                          for e in evs]
    loaded = pt.ProgramTrace(spans, [ops], None)  # the host's clock: lead 0
    monkeypatch.setattr(pt, "load", lambda p: loaded)
    monkeypatch.setattr(pt, "host_events",
                        lambda p: (spans,) + _runtime(device(modules)))
    return _View(device(modules), device(ops), path)


@pytest.mark.parametrize("shape", ["parent", "change"])
def test_a_prefill_row_holds_every_operation_of_its_program(monkeypatch,
                                                            shape):
    from chipbench import scopes as sc
    from chipbench import trace_reduce as tr

    view = _chunk_view(monkeypatch, shape, "rows:" + shape)
    spans = pt.load("x").spans
    wire = {sp[0]: sp for sp in spans if sp[3].get("step") == 3}
    pre, dec = wire[pt.PREFILL], wire[pt.DECODE]
    fetches = [sp for sp in spans if sp[0] == st.FETCH
               and pre[1] <= sp[1] < pre[1] + pre[2]]
    # wire.prefill closes after its own fetch and before wire.decode opens
    assert len(fetches) == 1
    assert fetches[0][1] + fetches[0][2] <= pre[1] + pre[2] <= dec[1]
    ops = pt._window_ops("ops:" + shape, *WINDOW)
    inside, = tr.events_inside(ops, [pre], pt.PREFILL)
    assert {o[0] for o in PRE_OPS} <= {o[0] for o in inside}
    scopes = ("moe.experts", "attn.core")
    row, = sc._scope_rows("rows:" + shape, pt.PREFILL, *WINDOW, scopes)
    assert row.by["moe.experts"] == pytest.approx(16 * MS)
    assert row.facts["tokens"] == 8
    if shape == "parent":  # the call is read before the next is made
        assert row.busy == pytest.approx(16 * MS)
    else:  # ... with the decode program's first operations: never fewer
        assert row.busy == pytest.approx((16 + 3.3) * MS)
        assert row.by["attn.core"] == pytest.approx(3.3 * MS)
    assert view.window == WINDOW


@pytest.mark.parametrize("shape", ["parent", "change"])
def test_an_overlapped_decode_call_is_left_out_of_the_calls(monkeypatch,
                                                            shape):
    view = _chunk_view(monkeypatch, shape, "calls:" + shape)
    t = st.of(view)
    assert t.lead_bounds == pytest.approx((0.8 * MS, 1.2 * MS))
    # no launch inside its wire.decode: the overlapped call is no call
    assert [c.step for c in t.calls] == (
        [1, 2, 3, 4, 5] if shape == "parent" else [1, 2, 4, 5])
    # ... and the medians are those of the decode-only calls (EXPECTED)
    for c in t.calls:
        if c.step != 3:
            assert c.own[1] - c.launch == pytest.approx(1.6 * MS)
            assert c.fetched - c.own[2] == pytest.approx(2.1 * MS)
    assert st.decode_dispatch_latency_ms(view) == pytest.approx(1.6)
    assert st.decode_completion_latency_ms(view) == pytest.approx(2.1)
    assert st.device_programs_per_decode_call(view) == 4
    assert t.prefill_steps == {3}  # (1, 2) and (4, 5) are back to back
    assert st.host_between_calls_ms(view) == pytest.approx(2.5)


@pytest.mark.parametrize("shape, host_ms, share", [
    ("parent", 40 - 23, 0.0), ("change", 35 - 23, 100.0)])
def test_the_chunk_steps_readers(monkeypatch, shape, host_ms, share):
    view = _chunk_view(monkeypatch, shape, "chunk:" + shape)
    host = R.load_reader("chunk_step_host_ms")
    assert [sp[1] for sp in host.chunk_steps(view)] == [100 * MS]
    assert host.read(view) == pytest.approx(host_ms)
    together = R.load_reader("chunk_steps_together_share").read
    assert together(view) == pytest.approx(share)
    # a chunk step whose device events the trace lost (the profiler's
    # buffer was full): counted as a step, left out of the host's time
    spans = pt.load("x").spans
    said = {"calls": "together"} if share else {}
    spans += _ns([("uccl.engine.step", 180, 15, said),
                  ("uccl.wire.prefill", 181, 8, {"step": 9}),
                  ("uccl.wire.decode", 190, 4, {"step": 9})])
    assert len(host.chunk_steps(view)) == 2
    assert host.read(view) == pytest.approx(host_ms)
    assert together(view) == pytest.approx(share)

    class NoTrace:  # a traced run of a program without spans
        record = {"trace_path": None}
        window = None

    assert host.read(NoTrace) is None and together(NoTrace) is None
    # a window without a chunk step: nothing to take a median of
    view.window = (0.0, 50.0 * MS)
    assert host.read(view) is None and together(view) is None


def test_the_two_chunk_step_readings_are_listed_in_every_cell():
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    last = bench["per_layer"][-2:]
    assert [m["name"] for m in last] == ["chunk_step_host_ms",
                                         "chunk_steps_together_share"]
    for m in last:
        assert m["workloads"] == cells and m["moves"] == "itl_p90_ms"
        assert (m["layer"], m["source"]) == ("serving engine", "program_span")
