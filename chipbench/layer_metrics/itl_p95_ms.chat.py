"""Gap between consecutive output tokens, 95th percentile of the pooled gaps
that end inside the window. Until PR 30 this cell's end-to-end metric. On
this cell's schedule 82 % of the gaps lie behind a decode-only step (20.4
ms), 16.4 % behind a one-row prefill program (33.7-35.0) and 1.4 % behind a
wider one (36.5 and more): the 95th percentile is inside the second kind but
three quarters of the way up it, where the steps a busy host delays collect
— beside 13 busy processes the 90th percentile moved 0.1-0.4 % and this one
0.4-2 %, and the driver's check of PR 30 read it 4.2 / 2.1 % apart from run
to run (PERF.md section 6). The cell is judged on ``itl_p90_ms``, the
middle of that kind; this one stays on the record, where a host that delays
steps shows."""


def read(view):
    return view.record["e2e"].get("itl_p95_ms")
