"""Gap between consecutive output tokens, 95th percentile of the pooled gaps
that end inside the window. Until PR 30 this cell's end-to-end metric. A gap
here is 22.3 ms (the decode program alone), 46.4 (behind a one-row prefill
program) or 59.5 and more (behind a two-row or the pool's), and the last
kind is about one gap in twenty: by the arrangement the 95th percentile sits
at 46.4 or at 59.5-60.0 (+28 %; 4 of 24 seeded runs, PERF.md section 6), an
edge no bound covers. The cell is judged on ``itl_p90_ms``, inside the
second kind; this one stays on the record, where the share of gaps behind
the slower programs shows."""


def read(view):
    return view.record["e2e"].get("itl_p95_ms")
