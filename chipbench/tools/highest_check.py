"""The PROGRAM computed in full float32 against the ``published`` reference,
on the chip, once:

    python -m chipbench.tools.highest_check <workload> <seed> <prompt_tokens> <new_tokens>

A benchmark run holds the served tokens to the reference at the precision
the configuration states (products at XLA's default: one bfloat16 pass), so
its gaps are those of rounding. Here the same objects — the runner's
``build``, its engine, its programs — are traced under
``default_matmul_precision("highest")`` and serve one request of
``prompt_tokens`` + ``new_tokens`` drawn from ``seed``; the reference runs
that request at ``highest`` too, and what is left between them is summation
order: one line with the gaps of the served tokens (``gap_max`` and the share
off the reference's best). Not part of a benchmark run."""

import json
import os
import sys


def main(workload, seed, prompt_tokens, new_tokens):
    import jax
    import numpy as np

    from chipbench import run as R
    from chipbench import spans as sp
    from chipbench.runners import serve

    seed, n_p, n_o = int(seed), int(prompt_tokens), int(new_tokens)
    bench = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    cell, cfg, mix = R.load_cell(bench, workload)
    R.enable_compile_cache()
    R.require_chip(cell["chips"])
    runner = R.runner_for(cfg)
    rec = sp.Recorder(annotate=False)
    with jax.default_matmul_precision("highest"):
        engine, backend, vocab = runner.build(cfg, seed, rec)
        prompt = np.random.default_rng([seed, 0x416]).integers(
            0, vocab, n_p).astype(np.int32)
        req = engine.submit(prompt, max_new_tokens=n_o)
        engine.drain()
        sample = [(prompt, list(req.out_tokens))]
        engine.close()
        del engine, backend
        jax.clear_caches()
    gaps = runner.reference_gaps(
        cfg, seed, sample,
        mix["prompt_len"]["max"] + mix["output_len"]["max"],
        mix["output_len"]["max"])
    print("highest_check " + json.dumps({
        "workload": workload, "seed": seed, "prompt_tokens": n_p,
        "new_tokens": len(sample[0][1]),
        **{r: serve.gap_numbers(gaps[r]["served"],
                                cfg["correct"]["clear_gap"])
           for r in serve.REFERENCES}}), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
