"""Drive ``run_cell`` without the look for a chip, on fixture files."""

import json
import os
import time

from chipbench import run as R

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")

BENCH = {
    "end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "ttft_mean_ms", "unit": "ms", "workloads": ["tiny.chat"]},
        {"name": "serve_tok_s", "unit": "tokens/s",
         "workloads": ["tiny.chat", "tiny.backlog"]},
    ],
    "per_layer": [],
}


def fixture(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return json.load(f)


def run(workload, cfg, mix, *, seed=2**31 + 77, seconds=1.5, chips=1,
        controls=(), lines=None):
    ctx = R.Ctx(cfg=cfg, mix=mix, seed=seed, seconds=seconds, trace=False,
                t_start=time.time(), chips=chips,
                peaks=R.load_json(os.path.join(R.HERE, "peaks.json"))["TPU v5 lite"],
                trace_dir="", compile_counter=R.CompileCounter(),
                controls=tuple(controls),
                log=(lines.append if lines is not None else (lambda s: None)))
    return R.run_cell(BENCH, workload, cfg, mix, ctx)


def clear_trace_caches():
    """Between two hand-made traces under one path: what the readers cache
    by path and window."""
    from chipbench import program_trace as pt
    from chipbench import scopes as sc

    sc._scope_rows.cache_clear()
    pt._window_ops.cache_clear()


def readings_of(cell: str):
    """The per-layer entries of BENCHMARK.json that list ``cell``."""
    bench = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    return R.metrics_for(bench["per_layer"], cell)
