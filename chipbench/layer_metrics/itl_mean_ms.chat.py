"""Gap between consecutive output tokens, mean over the same pooled gaps as
``itl_p95_ms``. A step is either a decode program alone (20.6 ms) or a
decode program behind a prefill program over the prefilling slots' rows
(34.6 ms behind the one-row program since PR 28; the pool's with three or
more prefilling); the 95th percentile sits on the second kind until fewer
than 5 % of the gaps carry one, so the mean is what shows the mix of the
two moving."""


def read(view):
    return view.record["e2e"].get("itl_mean_ms")
