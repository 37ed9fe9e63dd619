"""Gap between consecutive output tokens, 95th percentile of the pooled gaps
that end inside the window: further up the kinds of step than the
end-to-end ``itl_p90_ms``, where the share of gaps behind the slower
prefill programs and the steps a busy host delays show (PERF.md section 2)."""


def read(view):
    return view.record["e2e"].get("itl_p95_ms")
