"""The reducer's arithmetic on hand-made events, and its readings of a
small recorded trace of the chat cell (0.3 s cut from a chip run of PR 23,
operation names cut to 96 characters)."""

import json
import os

import pytest

from chipbench import trace_reduce as tr

from helpers import FIXTURES


def test_busy_is_the_union():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 1)]
    assert tr.merged_intervals(ev) == [(0, 15), (30, 35)]
    assert tr.busy_ns(ev) == 20
    assert tr.busy_ns(tr.clip(ev, 8, 32)) == 7 + 2


def test_idle_gaps_go_to_the_innermost_covering_span():
    ops = [("x", 10, 10), ("y", 40, 10)]
    spans = [("chipbench.window", 0, 100), ("chipbench.engine_step", 0, 60),
             ("chipbench.backend_decode", 18, 24),
             ("chipbench.wait_arrival", 60, 40)]
    rows = dict(tr.idle_gaps(ops, 0, 100, spans))
    # [0,10) step; [20,40) decode (inside step, shorter wins); [50,60) step;
    # [60,100) waiting for an arrival
    assert rows == {"chipbench.engine_step": 20e-9,
                    "chipbench.backend_decode": 20e-9,
                    "chipbench.wait_arrival": 40e-9}


def test_collective_exposure():
    ops = [("%fusion.1 = f32[8] fusion(...)", 0, 10),
           ("%all-to-all.2 = bf16[4] all-to-all(...)", 8, 10),
           ("%all-reduce-start.1 = f32[2] all-reduce-start(...)", 30, 4),
           ("%all_to_all_fusion = bf16[4] fusion(...)", 50, 1),
           ("%psum.9 = f32[2] all-reduce(...)", 60, 3)]
    total, bare = tr.collective_time(ops, ("all-to-all",))
    assert (total, bare) == (10, 8)
    total, bare = tr.collective_time(ops, ("all-reduce",))
    assert (total, bare) == (7, 7)
    assert tr.is_collective("%all_to_all.48 = bf16[4,2] all-to-all(bf16[4,2] %x)",
                            ("all-to-all",))
    mods = [("jit_step(1)", 0, 50), ("jit_norms(2)", 60, 1),
            ("jit_step(1)", 70, 50), ("jit_step(1)", 130, 50)]
    assert tr.main_program_runs(mods, 0, 200) == 3
    assert tr.main_program_runs(mods, 25, 140) == 0.5 + 1 + 0.2
    assert tr.collective_ms_per_run(ops, 2.0, ("all-to-all",),
                                    exposed=True) == 4 / 1e6


def test_short_names():
    full = ("%fusion.11 = f32[8,32,4096]{2,1,0:T(8,128)S(1)} fusion(f32[1,1,8,4096,"
            "14336]{4,3,2,1,0:T(8,128)} %p__blocks____we_up__.1, f32[8,32,14336]"
            "{2,1,0} %fusion.15), kind=kOutput, calls=%fused_computation.11")
    assert tr.short_op_name(full) == "%fusion.11 f32[8,32,4096] fusion <blocks_we_up>"
    assert tr.short_op_name("odd") == "odd"


@pytest.fixture(scope="module")
def chat():
    with open(os.path.join(FIXTURES, "chat.trace.json")) as f:
        return json.load(f)


def test_recorded_chat_trace(chat):
    planes = tr.device_planes(chat)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    ops = tr.line_events(planes[0], tr.OPS_LINE)
    spans = tr.host_spans(chat)
    assert tr.window(chat) is not None
    dec = tr.busy_per_span(ops, spans, "chipbench.backend_decode")
    pre = tr.busy_per_span(ops, spans, "chipbench.backend_prefill")
    assert len(dec) == 10 and len(pre) == 2
    # one decode program is 17.6 ms of device time inside a 20-21 ms host
    # span; one [16, 64] prefill program 47.2 ms inside 50.2 ms
    # (the last span is cut by the fixture's edge)
    assert all(17.4e6 < b < 17.8e6 and 20.0e6 < s < 21.5e6 for b, s, _ in dec[:-1])
    assert all(47.0e6 < b < 47.5e6 and 50.0e6 < s < 50.5e6 for b, s, _ in pre)
    step = tr.busy_per_span(ops, spans, "chipbench.engine_step")
    assert {"decoding", "kv_rows"} <= set(step[0][2])
    t0 = min(e[1] for e in ops)
    t1 = max(e[1] + e[2] for e in ops)
    busy = tr.busy_ns(ops)
    assert 0.85 < busy / (t1 - t0) < 0.92
    gaps = tr.idle_gaps(ops, t0, t1, spans)
    # most of the idle time is inside the backend's decode call: dispatch
    # before the program starts and the host read after it ends
    assert gaps[0][0] == "chipbench.backend_decode"
    assert abs(sum(g[1] for g in gaps) * 1e9 - ((t1 - t0) - busy)) < 1.0
    assert tr.top_ops(ops, 1)[0][0].startswith("%fusion.11 f32[8,32,4096]")


def test_engine_host_time_counts_the_windows_steps_alone(chat):
    """The trace runs on through the drain while the operations are cut to
    the window: a step that starts past the window's end would count whole
    as host time (PERF.md section 7, found in PR 24)."""
    from chipbench import run as R

    view = R.TraceView(chat, {}, {}, {}, {}, 1)
    steps = [sp for sp in view.host_spans if sp[0] == "chipbench.engine_step"]
    assert len(steps) == 10
    # the measured window closes as the seventh step starts: three steps of
    # the fixture (and the seventh) lie in the drain
    view.window = (view.window[0], steps[6][1])
    read = R.load_reader("engine_host_ms_per_step").read
    got = read(view)
    inside = tr.busy_per_span(view.ops(0), steps[:6], "chipbench.engine_step")
    assert got == pytest.approx(
        sum(span - busy for busy, span, _ in inside) / 6 / 1e6)
    assert 2.5 < got < 4.5  # 17.6 of 20.7 ms a decode step, 64.8 of 70.9
    # with the drain's four steps counted whole it read nearly four times that
    everything = tr.busy_per_span(view.ops(0), steps, "chipbench.engine_step")
    assert sum(s - b for b, s, _ in everything) / 10 / 1e6 > 3 * got
    view.window = None
    assert read(view) is None
