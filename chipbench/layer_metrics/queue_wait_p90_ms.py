"""Due time -> admission into a KV slot (the engine's ``Request.t_admit``),
90th percentile over the window's requests: the scheduler's share of TTFT."""


def read(view):
    return view.record["e2e"].get("queue_wait_p90_ms")
