"""Arrangements of a serving cell's traffic, one window each, on one engine:

    python -m chipbench.tools.arrangements <workload> <seconds> <rate>:<order_seed> ...

For each pair the mix is offered at ``rate`` in the order ``order_seed``
draws (``generators/requests.py``: the same multiset of lengths and gaps
whatever the order), through the configuration's own runner's ``build``;
window, drain cap and reduction are the cell's. One line per pair with what
a knee sweep reads (backlog by quarter, unfinished, failed) and what the
choice of a pinned ``order_seed`` reads: ``ttft_mean_ms``, ``itl_p90_ms`` and
``itl_p80_to_p99_ms``, point by point, so that the edges between the kinds
of step show. ``knee_sweep_runner`` draws its orders from its own seeds and
prints no percentile above the 95th; this is the same loop with both."""

import json
import os
import sys

import numpy as np


def main(workload, seconds, *pairs):
    from chipbench import run as R
    from chipbench import spans as sp
    from chipbench.generators import requests as gen
    from chipbench.runners import serve

    seconds = float(seconds)
    bench = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    cell, cfg, mix = R.load_cell(bench, workload)
    R.enable_compile_cache()
    R.require_chip(cell["chips"])
    rec = sp.Recorder(annotate=False)
    engine, backend, vocab = R.runner_for(cfg).build(cfg, 7, rec)
    serve.warm(engine, cfg["serving"]["prefill_chunk"])
    for arg in pairs:
        rate, _, order = arg.partition(":")
        m = dict(mix, rate_rps=float(rate), order_seed=int(order))
        traffic = gen.generate(m, 7, seconds, vocab)
        rec.spans.clear()
        win = serve.drive(engine, traffic, seconds, mix["drain_s"], rec)
        served = win["served"]
        e2e = serve.reduce_window(served, seconds, end_s=win["end_s"])

        def waiting(t):
            return sum(1 for sv in served if sv.req is not None
                       and sv.submit_s <= t and (
                           sv.req.t_admit is None
                           or sv.req.t_admit - rec.t0 > t))

        q = [np.mean([waiting(t)
                      for t in np.linspace(a * seconds, b * seconds, 50)])
             for a, b in ((0.25, 0.5), (0.5, 0.75), (0.75, 1.0))]
        prefills = [a for n, _, _, a in rec.spans
                    if n == serve.NAME_PREFILL]
        print("arrangement " + json.dumps({
            "rate_rps": float(rate), "order_seed": int(order),
            "requests": len(served),
            "waiting_q2_q3_q4": [round(x, 2) for x in q],
            "unfinished_at_window_end": sum(
                1 for sv in served
                if not sv.stamps or sv.stamps[-1] > seconds),
            "failed": e2e["failed"],
            "ttft_mean_ms": e2e["ttft_mean_ms"],
            "ttft_p50_ms": e2e["ttft_p50_ms"],
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "queue_wait_p90_ms": e2e["queue_wait_p90_ms"],
            "itl_p50_ms": e2e["itl_p50_ms"], "itl_p90_ms": e2e["itl_p90_ms"],
            "itl_p95_ms": e2e["itl_p95_ms"],
            "itl_p80_to_p99_ms": e2e["itl_p80_to_p99_ms"],
            "serve_tok_s": e2e["serve_tok_s"],
            "offered_tok_s": (sum(p.size for p in traffic.prompts)
                              + float(np.sum(traffic.output_lens))) / seconds,
            "steps": win["steps"], "prefill_calls": len(prefills),
            "drain_s": win["end_s"] - seconds,
            "late_max_ms": e2e["late_max_ms"]}), flush=True)
        # the engine is reused: let what the drain cap cut short finish
        engine.drain()


if __name__ == "__main__":
    main(*sys.argv[1:])
