"""``decode_experts_read_share``: the program's own count of the experts a
decode step read, off its ``uccl.ep.experts`` span."""

import os

import pytest

from chipbench import program_trace as pt
from chipbench import run as R

MS = 1e6
NAME = "decode_experts_read_share"
CELLS = [m for m in R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))[
    "per_layer"] if m["name"] == NAME][0]["workloads"]


def _view(spans, monkeypatch):
    trace = pt.ProgramTrace(spans, [[("op", 1 * MS, 1 * MS, "jit(p)/x:")]])
    monkeypatch.setattr(pt, "load", lambda path: trace)

    class View:
        record = {"trace_path": "hand-made"}
        window = (0.0, 100 * MS)

    return View


@pytest.mark.parametrize("cell", CELLS)
def test_share_is_the_median_over_the_windows_decode_spans(cell, monkeypatch):
    """One reader for every cell listed: the span is the backend's, whatever
    the family."""
    count = pt.PREFIX + "ep.experts"
    spans = [
        (pt.DECODE, 0.0, 10 * MS, {"n": 1}),
        (count, 9 * MS, 0.001 * MS, {"experts_read": 4, "experts_held": 128}),
        (pt.PREFILL, 10 * MS, 10 * MS, {"n": 1, "rows": 1}),
        (pt.DECODE, 20 * MS, 10 * MS, {"n": 3}),
        (count, 29 * MS, 0.001 * MS, {"experts_read": 16,
                                      "experts_held": 128}),
        (pt.DECODE, 40 * MS, 10 * MS, {"n": 2}),
        (count, 49 * MS, 0.001 * MS, {"experts_read": 8,
                                      "experts_held": 128}),
        # a step that starts after the window closed does not count
        (pt.DECODE, 120 * MS, 10 * MS, {"n": 8}),
        (count, 129 * MS, 0.001 * MS, {"experts_read": 128,
                                       "experts_held": 128}),
    ]
    read = R.load_reader(NAME).read
    assert read(_view(spans, monkeypatch)) == 100.0 * 8 / 128


def test_a_program_that_reports_no_count_reads_none(monkeypatch):
    spans = [(pt.DECODE, 0.0, 10 * MS, {"n": 1, "kv_rows": 100})]
    read = R.load_reader(NAME).read
    assert read(_view(spans, monkeypatch)) is None

    class Untraced:
        record = {"trace_path": None}
        window = None

    assert read(Untraced) is None
