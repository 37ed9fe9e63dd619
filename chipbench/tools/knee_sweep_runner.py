"""``knee_sweep`` for any serving configuration: the same sweep, with
``build`` taken from the configuration's own runner (``knee_sweep.py`` calls
``runners.serve.build``, which is the Mixtral configuration's):

    python -m chipbench.tools.knee_sweep_runner <workload> <seconds> <rate>[:<seed>] ...

A runner offers ``build(cfg, seed, rec)``; window, drain and reduction are
``runners/serve.py``'s. One line per rate, as ``knee_sweep`` prints them."""

import json
import os
import sys

import numpy as np


def main(workload, seconds, *rates):
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
    for k, arg in enumerate(rates):
        rate, _, seed = arg.partition(":")
        rate, seed = float(rate), int(seed) if seed else 1000 + k
        # a sweep asks every arrangement it tries: the order from ITS seed
        m = dict(mix, rate_rps=rate)
        m.pop("order_seed", None)
        traffic = gen.generate(m, seed, seconds, vocab)
        rec.spans.clear()
        win = serve.drive(engine, traffic, seconds, 90.0, rec)
        served = win["served"]
        e2e = serve.reduce_window(served, seconds)

        def waiting(t):
            return sum(1 for sv in served if sv.req is not None
                       and sv.submit_s <= t and (
                           sv.req.t_admit is None
                           or sv.req.t_admit - rec.t0 > t))

        q = [np.mean([waiting(t)
                      for t in np.linspace(a * seconds, b * seconds, 50)])
             for a, b in ((0.25, 0.5), (0.5, 0.75), (0.75, 1.0))]
        print("sweep " + json.dumps({
            "rate_rps": rate, "seed": seed, "requests": len(served),
            "waiting_q2_q3_q4": [round(x, 2) for x in q],
            "unfinished_at_window_end": sum(
                1 for sv in served
                if not sv.stamps or sv.stamps[-1] > seconds),
            "ttft_p50_ms": e2e["ttft_p50_ms"],
            "ttft_p90_ms": e2e["ttft_p90_ms"],
            "ttft_mean_ms": e2e["ttft_mean_ms"],
            "itl_p50_ms": e2e["itl_p50_ms"], "itl_p95_ms": e2e["itl_p95_ms"],
            "serve_tok_s": e2e["serve_tok_s"],
            "offered_tok_s": (sum(p.size for p in traffic.prompts)
                              + float(np.sum(traffic.output_lens))) / seconds,
            "steps": win["steps"], "drain_s": win["end_s"] - seconds,
            "failed": e2e["failed"], "late_max_ms": e2e["late_max_ms"]}),
            flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
