"""Due time -> first token, 90th percentile over all the window's requests:
the tail a user feels, which follows the longest few prompts — too few
samples to be held to a bound (PERF.md section 6), so it stands here,
beside the end-to-end ``ttft_mean_ms``."""


def read(view):
    return view.record["e2e"].get("ttft_p90_ms")
