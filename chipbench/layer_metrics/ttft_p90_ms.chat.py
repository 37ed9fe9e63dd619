"""Due time -> first token, 90th percentile over all the window's requests
(71 at 51 s: seven beyond it). The tail a chat user feels — and too few
samples to be held to a bound: from one arrangement of the arrivals to
another it swung two to five times what the mean did (PR 23's sets, PERF.md
section 6), so it stands here, beside the end-to-end ``ttft_mean_ms``."""


def read(view):
    return view.record["e2e"].get("ttft_p90_ms")
