"""Gap between consecutive output tokens, mean over the same pooled gaps as
``itl_p95_ms``. A step is a decode program alone or a decode program behind
a prefill program over the prefilling slots' rows; a percentile sits on one
kind until the share of the other passes it, so the mean is what shows the
mix of the two moving."""


def read(view):
    return view.record["e2e"].get("itl_mean_ms")
