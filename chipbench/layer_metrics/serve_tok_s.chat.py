"""(Prompt tokens of the requests whose first token fell inside the window
+ output tokens emitted inside it) / window. Below the knee it restates the
offered load, and it swings with which long request the window's end cuts
(5 % from one arrangement of the arrivals to another in PR 23's sets,
PERF.md section 6), so it is not held to a bound here: a guard that reads low when the engine falls behind (``ttft_mean_ms``,
timed from when a request was due, is the end-to-end guard for that)."""


def read(view):
    return view.record["e2e"].get("serve_tok_s")
