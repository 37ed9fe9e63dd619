"""Readings a cell's ``correct`` limits are set from, on the chip:

    python -m chipbench.tools.calibrate [--controls=bf16,int8] <workload> <seconds> <seed> [<seed> ...]

One process. For each seed the cell's runner makes a run with a window of
<seconds> (the cell's own traffic at its own rate, then the drain) and
holds what the timed path produced against the plain reference — and the
same against the lower-precision control the configuration names (the
reference computed with bfloat16 weights, KV and activations): one line per
seed with the sound run's numbers and the control's (``--controls`` names
others of ``reference.quantize_weights``' kinds, one after the other).
PERF.md records the table and the limits chosen from it. Not part of a
benchmark run."""

import json
import os
import sys
import time


def main(workload, seconds, *seeds, controls=("bf16",)):
    from chipbench import run as R

    bench = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    cell, cfg, mix = R.load_cell(bench, workload)
    R.enable_compile_cache()
    peaks = R.require_chip(cell["chips"])
    counter = R.CompileCounter()
    runner = R.runner_for(cfg)
    for seed in (int(s) for s in seeds):
        ctx = R.Ctx(cfg=cfg, mix=mix, seed=seed, seconds=float(seconds),
                    trace=False, t_start=time.time(), chips=cell["chips"],
                    peaks=peaks, trace_dir="", compile_counter=counter,
                    controls=tuple(controls))
        rec = runner.run(ctx)
        print("calibrate " + json.dumps({
            "seed": seed,
            "sound": {n: v for n, v, _ in rec["compared"]},
            "numbers": rec["numbers"],
            "slowest_steps_s_at_s": rec.get("slowest_steps_s_at_s"),
            "controls": rec["control_numbers"],
            "check_tokens": rec.get("check_tokens"),
            "reference_s": rec["reference_s"],
            "e2e": {k: rec["e2e"].get(k) for k in (
                "ttft_mean_ms", "ttft_p90_ms", "itl_p95_ms", "itl_mean_ms",
                "serve_tok_s", "failed", "attempted")},
            "memory_peak_bytes": rec["memory_peak_bytes"],
        }), flush=True)


if __name__ == "__main__":
    args = sys.argv[1:]
    if args and args[0].startswith("--controls="):
        main(*args[1:], controls=args[0].split("=", 1)[1].split(","))
    else:
        main(*args)
