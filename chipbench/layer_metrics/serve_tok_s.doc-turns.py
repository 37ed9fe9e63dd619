"""(Prompt tokens of the requests whose first token fell inside the window
+ output tokens emitted inside it) / window. Below the knee it restates the
offered load; a guard that reads low when the engine falls behind."""


def read(view):
    return view.record["e2e"].get("serve_tok_s")
