"""Due time -> first token, 90th percentile over all the window's requests
(71 at 51 s: seven beyond it). The tail a chat user feels — and too few
samples to be held to a bound: it swings 5-12 % from seed to seed where the
mean swings 2 % (PERF.md section 2), so it stands here, beside the
end-to-end ``ttft_mean_ms``."""


def read(view):
    return view.record["e2e"].get("ttft_p90_ms")
