"""Due time -> first token, 90th percentile over the window's requests: with
prompts of 256-14,336 tokens it follows the longest few prompts."""


def read(view):
    return view.record["e2e"].get("ttft_p90_ms")
