"""(Prompt tokens of the requests whose first token fell inside the window
+ output tokens emitted inside it) / window. Below the knee it restates the
offered load and swings with which long request the window's end cuts, so
it is held to no bound: a guard that reads low when the engine falls behind
(``ttft_mean_ms``, timed from when a request was due, is the end-to-end
guard for that)."""


def read(view):
    return view.record["e2e"].get("serve_tok_s")
