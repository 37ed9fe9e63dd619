"""The serving runner of the ``brumby`` configurations (Brumby-14B-Base;
``"runner": "serve_brumby"``): the model description read from the published
keys (``MoEServeConfig.from_hf``: every layer a power retention whose
per-slot state is a cache group with NO position axis, a dense SwiGLU, no
expert layer, an untied head) -> ``MoEServer`` -> ``MoEBackend`` ->
``ServingEngine``, as ``uccl_tpu/serve.py --server --stack moe
--model-config <file>`` builds them. The window, its reduction, the sample
and the numbers compared are ``runners/serve.py``'s own, imported, and the
three padded lengths ``runners/serve_mimo.py``'s; what is new here is
``build`` (no expert queue to size), the reference call — another model, run
at the precisions the limits name — and the pool's bytes by cache group in
the record (``kv_pool_bytes``: the gauge at each group this model keeps).
"""

from __future__ import annotations

import gc
import json
import time

import numpy as np

from chipbench import spans as sp
from chipbench.generators import requests as gen
from chipbench.runners.serve import (
    NAME_DECODE, NAME_STEP, REFERENCES, SpannedBackend, check_sample, drive,
    gap_numbers, reduce_window, warm,
)
from chipbench.runners.serve_mimo import pad_lengths


def kv_pool_bytes(groups) -> dict:
    """The slot pool's bytes by cache group, from the program's own gauge
    (set when the pool is born) for the ``groups`` this model's layers keep
    (the gauge is the process's: another model's pool leaves its groups
    there); {} where the gauge was never set."""
    from uccl_tpu import obs

    g = obs.gauge("serving_kv_pool_bytes")
    return {k: v for k in groups if (v := g.get(group=k))}


def build(cfg: dict, seed: int, rec):
    """(engine, backend, vocab size): weights drawn on the device from the
    seed in one jitted call (float32 draws stored in the configuration's
    ``precision.weights``), placed and wrapped as serve.py does."""
    import jax

    from uccl_tpu.models.moe_inference import (
        MoEServeConfig, MoEServer, init_params,
    )
    from uccl_tpu.parallel.mesh import MeshConfig, make_mesh
    from uccl_tpu.serving import MoEBackend, ServingEngine

    s = cfg["serving"]
    mcfg = MoEServeConfig.from_hf(
        cfg, param_dtype=cfg["precision"]["weights"])
    world = s["world"]
    mesh = make_mesh(MeshConfig(dp=world), jax.devices()[:world])
    server = MoEServer(mcfg, mesh)
    params = jax.jit(lambda key: server.shard_params(init_params(key, mcfg)))(
        jax.random.PRNGKey(seed))
    backend = MoEBackend(
        server, params,
        batch_local=s["slots"] // world, max_seq=s["max_seq"],
        decode_impl=s["decode_impl"],
    )
    del params
    engine = ServingEngine(SpannedBackend(backend, rec),
                           prefill_chunk=s["prefill_chunk"])
    return engine, backend, mcfg.vocab


def reference_gaps(cfg: dict, seed: int, sample, pad_to: int, max_out: int,
                   controls=(), references=REFERENCES):
    """Run the plain reference over each sampled request's prompt with its
    served tokens, twice: ``published`` is the model's forward in full
    float32 (``highest``), ``stated`` the same forward at the products'
    precision the configuration states (``precision.matmul``). A request
    runs at the least of three padded lengths that holds it and its logits
    are taken at the ``max_out`` rows that could have produced served
    tokens. The control (``bf16``: bfloat16 activations) reads the gap of
    the token IT puts first, at the same rows. Returns {reference:
    {"served": gaps, <control>: gaps}}, each a flat array over all served
    positions of the sample. ``references``: which of the two to run (a
    benchmark run runs those its limits name: ``published`` at ``highest``
    is six MXU passes a product, six sevenths of the reference's time)."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import brumby as ref

    weights = jax.jit(lambda k: ref.init_weights(k, cfg))(
        jax.random.PRNGKey(seed))
    precision = {"published": "highest",
                 "stated": cfg["precision"]["matmul"]}

    def padded(prompt, tokens):
        """(the request as one padded sequence, the rows whose logits chose
        the served tokens padded to ``max_out``, the served tokens padded
        alike, how many of them are real)"""
        n_p, n_o = len(prompt), len(tokens)
        size = min(n for n in pad_lengths(pad_to) if n >= n_p + n_o)
        seq = np.zeros(size, np.int32)
        seq[:n_p] = prompt
        seq[n_p:n_p + n_o - 1] = tokens[:-1]
        rows = np.minimum(n_p - 1 + np.arange(max_out), size - 1)
        following = np.zeros(max_out, np.int32)
        following[:n_o] = tokens
        return seq, rows.astype(np.int32), following, n_o

    out = {r: {"served": [], **{c: [] for c in controls}}
           for r in references}
    for prompt, tokens in sample:
        seq, rows, following, n_o = padded(prompt, tokens)
        logits = {r: ref.forward_logits(weights, seq, cfg, rows=rows,
                                        precision=precision[r])
                  for r in references}
        for r in references:
            out[r]["served"].append(np.asarray(
                ref.served_token_gaps(logits[r], following))[:n_o])
        for c in controls:
            if c != "bf16":
                raise ValueError(f"unknown control precision {c!r}")
            first = jnp.argmax(ref.forward_logits(
                weights, seq, cfg, rows=rows, dtype=jnp.bfloat16,
                precision="default"), axis=-1).astype(jnp.int32)
            for r in references:
                out[r][c].append(np.asarray(
                    ref.served_token_gaps(logits[r], first))[:n_o])
    return {r: {k: np.concatenate(v) if v else np.zeros(0)
                for k, v in by.items()} for r, by in out.items()}


def run(ctx) -> dict:
    """One run of a serving cell. Returns the run record the metric readers
    take their numbers from (the keys ``runners/serve.py``'s record has)."""
    import jax

    cfg, mix = ctx.cfg, ctx.mix
    rec = sp.Recorder(annotate=ctx.trace)
    phases = {"process_to_runner": time.time() - ctx.t_start}
    t = time.perf_counter()
    engine, backend, vocab = build(cfg, ctx.seed, rec)
    phases["build"] = time.perf_counter() - t
    t = time.perf_counter()
    warm(engine, cfg["serving"]["prefill_chunk"])
    phases["warm"] = time.perf_counter() - t
    traffic = gen.generate(mix, ctx.seed, ctx.seconds, vocab)
    too_long = [j for j, p in enumerate(traffic.prompts)
                if p.size + traffic.output_lens[j] > cfg["serving"]["max_seq"]]
    if too_long:
        raise SystemExit(f"traffic exceeds max_seq: requests {too_long[:5]}")
    tracer = ctx.make_tracer() if ctx.trace else None
    gc.collect()
    compiles = ctx.compile_counter.snapshot()
    setup_s = time.time() - ctx.t_start
    win = drive(engine, traffic, ctx.seconds, mix["drain_s"], rec, tracer)
    compiles_in_window = ctx.compile_counter.snapshot() - compiles
    served = win["served"]
    e2e = reduce_window(served, ctx.seconds, mix.get("attempted", "due"),
                        win["end_s"])
    e2e["setup_s"] = setup_s
    leaked = engine.pool.leaked()
    queued = engine.sched.qsize
    # every request that held a slot and ended has the tokens it asked for
    short = sum(1 for sv in served if sv.req is not None and sv.req.is_done()
                and sv.req.t_admit is not None
                and len(sv.req.out_tokens) != sv.req.max_new_tokens)
    decode_active = [a["active"] for n, _, _, a in rec.spans
                     if n == NAME_DECODE]
    memory_peak = ctx.memory_peak()
    in_use = (jax.local_devices()[0].memory_stats() or {}).get("bytes_in_use")
    picks = check_sample(served, ctx.seed, cfg["correct"]["sample_requests"])
    sample = [(np.asarray(served[j].req.prompt),
               list(served[j].req.out_tokens)) for j in picks]
    pool_bytes = kv_pool_bytes(sorted(set(backend.cfg.layer_kinds)))
    ctx.log("chipbench: " + json.dumps({
        "sample_positions": sorted(len(p) + len(o) for p, o in sample),
        "kv_pool_bytes": pool_bytes}))
    # the program's state goes before the reference's comes
    engine.close()
    del engine, backend
    for sv in served:
        sv.req = None
    jax.clear_caches()
    gc.collect()
    t_ref = time.perf_counter()
    # the references the limits name (<reference>_<number>), in their order
    references = tuple(r for r in REFERENCES if any(
        name.startswith(r + "_") for name in cfg["correct"]["limits"]))
    gaps = reference_gaps(
        cfg, ctx.seed, sample,
        mix["prompt_len"]["max"] + mix["output_len"]["max"],
        mix["output_len"]["max"], controls=ctx.controls,
        references=references)
    clear = cfg["correct"]["clear_gap"]
    numbers = {r: gap_numbers(gaps[r]["served"], clear) for r in references}
    control_numbers = {c: {r: gap_numbers(gaps[r][c], clear)
                           for r in references} for c in ctx.controls}
    # a limit is named <reference>_<number>: stated_clear_miss_share ...
    compared = [(name, numbers[name.split("_", 1)[0]][name.split("_", 1)[1]],
                 limit) for name, limit in cfg["correct"]["limits"].items()]
    compared += [
        ("short_or_long_requests", short, 0),
        ("leaked_slots", leaked, 0),
        ("queued_after_drain", queued, 0),
        ("requests_not_compared",
         max(0, min(cfg["correct"]["sample_requests"],
                    e2e["attempted"] - e2e["failed"]) - len(sample))
         + (0 if sample else 1), 0),
    ]
    slow = sorted(((t1 - t0, t0 - rec.t0) for n, t0, t1, _ in rec.spans
                   if n == NAME_STEP), reverse=True)[:5]
    return {
        "e2e": e2e, "compared": compared, "numbers": numbers,
        "setup_phases_s": {k: round(v, 3) for k, v in phases.items()},
        "slowest_steps_s_at_s": [[round(d, 4), round(at, 3)] for d, at in slow],
        "reference_s": time.perf_counter() - t_ref,
        "check_tokens": numbers[references[0]]["tokens"],
        "check_requests": len(sample),
        "control_numbers": control_numbers,
        "memory_peak_bytes": memory_peak,
        "kv_pool_bytes": pool_bytes,
        "memory_in_use_after_window_bytes": in_use,
        "compiles_in_window": compiles_in_window,
        "steps": win["steps"], "drain_s": max(0.0, win["end_s"] - ctx.seconds),
        "decode_active": decode_active,
        "spans": rec.spans, "span_t0": rec.t0,
        "trace_path": tracer.path if tracer is not None else None,
        "trace_window_s": tracer.window_s if tracer is not None else None,
    }
