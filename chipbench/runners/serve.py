"""The serving runner: one configuration with ``"runner": "serve"`` under one
request mix, through the program's own objects — ``MoEServeConfig`` ->
``MoEServer`` -> ``MoEBackend`` -> ``ServingEngine`` — built the way
``uccl_tpu/serve.py --server --stack moe --prefill-chunk C`` builds them
(its CLI has no flag for ``rope_theta``/``norm_eps``, so the objects are
made here, not through ``main``).

The load generator and the engine share one thread (the engine is not
thread-safe and a step holds the interpreter anyway): requests are submitted
between steps, every latency is timed from when the request was DUE, and
how late the generator ran is reported. The loop stamps each request's new
tokens after every ``engine.step()``, when a client could first see them.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import List

import numpy as np

from chipbench import spans as sp
from chipbench.generators import requests as gen
from chipbench.stats import percentile

NAME_STEP = "chipbench.engine_step"
NAME_PREFILL = "chipbench.backend_prefill"
NAME_DECODE = "chipbench.backend_decode"
NAME_SUBMIT = "chipbench.submit"
NAME_WAIT = "chipbench.wait_arrival"


class SpannedBackend:
    """The program's backend with the benchmark's spans around the two calls
    into the model layer; everything else passes through."""

    def __init__(self, inner, rec):
        self._inner = inner
        self._rec = rec

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def prefill(self, *a, **kw):
        with self._rec.span(NAME_PREFILL):
            return self._inner.prefill(*a, **kw)

    def decode(self, tokens, active, **kw):
        with self._rec.span(NAME_DECODE, active=int(np.sum(active))):
            return self._inner.decode(tokens, active, **kw)


@dataclass
class Served:
    """What the window saw of one request."""
    due_s: float
    prompt_tokens: int  # known before the request is offered
    submit_s: float = float("nan")
    req: object = None
    stamps: List[float] = field(default_factory=list)  # one per output token


def build(cfg: dict, seed: int, rec):
    """(engine, backend, vocab size): weights drawn on the device from the
    seed in one jitted call, placed and wrapped as serve.py does."""
    import jax

    from uccl_tpu.models.moe_inference import (
        MoEServeConfig, MoEServer, init_params,
    )
    from uccl_tpu.parallel.mesh import MeshConfig, make_mesh
    from uccl_tpu.serving import MoEBackend, ServingEngine

    s = cfg["serving"]
    heads = cfg["num_attention_heads"]
    mcfg = MoEServeConfig(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=heads,
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        moe_experts=cfg["num_local_experts"],
        moe_topk=cfg["num_experts_per_tok"],
        moe_ffn=cfg["intermediate_size"],
        capacity_factor=s["capacity_factor"],
    )
    world = s["world"]
    mesh = make_mesh(MeshConfig(dp=world), jax.devices()[:world])
    server = MoEServer(mcfg, mesh)
    # drawn and laid out as the server wants them in ONE program, so the
    # chip never holds the drawn tree beside its placed copy (2 x 6.9 GB)
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


def warm(engine, chunk: int) -> None:
    """prefill -> decode -> prefill -> decode: the prefill programs (every
    rung on the engine's first chunked call, since PR 28) and the decode
    program, each also with the slot cache as the other leaves it (a pool
    born uncommitted came back placed, which compiled a second time inside
    a measured window once — PERF.md "Bring-up")."""
    for _ in range(2):
        engine.submit(np.zeros(2 * chunk + 1, np.int32), max_new_tokens=3)
        engine.drain()
    engine.reset_metrics()


def drive(engine, traffic, seconds: float, drain_s: float, rec,
          tracer=None) -> dict:
    """Offer the window's requests as they fall due, step the engine, stamp
    tokens; after the window, drain for at most ``drain_s``."""
    n = len(traffic.prompts)
    served = [Served(due_s=float(d), prompt_tokens=int(p.size))
              for d, p in zip(traffic.due_s, traffic.prompts)]
    live: List[int] = []
    i = 0
    steps = 0
    closed = False
    if tracer is not None:
        tracer.open()
    t0 = time.perf_counter()
    rec.t0 = t0
    while True:
        t = time.perf_counter() - t0
        if t > seconds + drain_s:
            break
        if t >= seconds and not closed:
            # the window has closed: nothing more is offered, and what still
            # waits for a slot is withdrawn; what holds a slot may finish
            closed = True
            i = n
            if tracer is not None:
                tracer.mark_end()
            for j in live:
                if served[j].req.t_admit is None:
                    engine.cancel(served[j].req.rid)
        if i < n and served[i].due_s <= t:
            with rec.span(NAME_SUBMIT):
                while i < n and served[i].due_s <= t:
                    served[i].submit_s = time.perf_counter() - t0
                    served[i].req = engine.submit(
                        traffic.prompts[i],
                        max_new_tokens=int(traffic.output_lens[i]))
                    live.append(i)
                    i += 1
        if engine.has_work():
            # the slots that will decode in this step, and the cached rows
            # they attend over (requests that have their first token)
            dec = [served[j].req for j in live if served[j].stamps]
            with rec.span(NAME_STEP, decoding=len(dec),
                          kv_rows=sum(int(r.prompt.size) + len(r.out_tokens)
                                      for r in dec)):
                engine.step()
            steps += 1
            t_seen = time.perf_counter() - t0
            still = []
            for j in live:
                sv = served[j]
                new = len(sv.req.out_tokens) - len(sv.stamps)
                if new > 0:
                    sv.stamps.extend([t_seen] * new)
                if not sv.req.is_done():
                    still.append(j)
            live = still
        elif i < n:
            with rec.span(NAME_WAIT):
                time.sleep(max(0.0, min(0.002, served[i].due_s - t)))
        else:
            break
    if tracer is not None:
        tracer.close()
    return {"served": served, "steps": steps,
            "end_s": time.perf_counter() - t0}


def reduce_window(served, seconds: float, attempted: str = "due",
                  end_s: float = None) -> dict:
    """The end-to-end readings of one window, and their sample counts.
    ``attempted`` (a mix's own key): "due" counts every request due in the
    window, "admitted" those given a slot before it closed (a backlog is
    offered beyond capacity on purpose; what never got a slot was not tried).
    ``end_s`` is when the run stopped watching (the drain's end): a counted
    request that had no first token by then waited at least that long.
    ``ttft_per_ktok_p50_ms`` is the median over those requests of TTFT per
    1,000 prompt tokens: a prompt is prefilled chunk by chunk, so its TTFT
    grows with its length, and the quotient is what a prefill step costs
    whatever the chunk; the median does not follow the few requests that met
    a rare slow step or waited for a slot (the mean does: ``ttft_mean_ms``).
    ``itl_p90_ms`` is the 90th percentile of the gaps ``itl_p95_ms`` is the
    95th of: where about one gap in twenty lies behind a slower kind of
    step, the 95th sits on the edge between two kinds and the 90th inside
    one, and the steps a busy host delays collect above the 95th before they
    reach the 90th (PERF.md section 2); ``itl_p80_to_p99_ms`` keeps the upper
    fifth of that distribution, point by point, for the log: where its edges
    lie and how far down the delayed steps reach. ``requests`` keeps each
    counted request's [prompt tokens, TTFT ms, 1 if it has a first token]
    for the log: what PERF.md's table of arrangements was reckoned from."""
    end_s = seconds if end_s is None else end_s
    ttft, requests, gaps, late, qwait = [], [], [], [], []
    prompt_tok = out_tok = finished = first_tokens = 0
    for sv in served:
        r = sv.req
        admitted = r is not None and r.t_admit is not None
        if not (admitted or attempted == "due"):
            continue  # a backlog's request that never got a slot
        if sv.stamps:
            first_tokens += 1
            ttft.append(sv.stamps[0] - sv.due_s)
        else:
            # due, and without a first token when the run stopped watching
            # (or never offered: the window closed on a late generator): it
            # waited at least that long, and stays in the mean and the tail
            ttft.append(end_s - sv.due_s)
        requests.append([sv.prompt_tokens, round(1e3 * ttft[-1], 3),
                         int(bool(sv.stamps))])
        if r is None:
            continue
        late.append(sv.submit_s - sv.due_s)
        if admitted:
            # the engine stamps admission on its own clock (perf_counter)
            qwait.append((r.t_admit - r.t_submit) + (sv.submit_s - sv.due_s))
        if sv.stamps and sv.stamps[0] < seconds:
            prompt_tok += int(r.prompt.size)
        gaps.extend(b - a for a, b in zip(sv.stamps, sv.stamps[1:])
                    if b < seconds)
        out_tok += sum(1 for t in sv.stamps if t < seconds)
        finished += r.is_done() and r.finish_reason == "length"
    n = len(ttft)
    gaps.sort()  # once, for the percentiles below
    return {
        "attempted": n, "failed": n - finished,
        "ttft_p50_ms": 1e3 * percentile(ttft, 50),
        "ttft_p75_ms": 1e3 * percentile(ttft, 75),
        "ttft_p90_ms": 1e3 * percentile(ttft, 90),
        "ttft_mean_ms": 1e3 * sum(ttft) / max(1, n),
        "ttft_per_ktok_p50_ms": 1e3 * percentile(
            [1e3 * t / r[0] for t, r in zip(ttft, requests)], 50),
        "itl_p50_ms": 1e3 * percentile(gaps, 50) if gaps else None,
        "itl_p90_ms": 1e3 * percentile(gaps, 90) if gaps else None,
        "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else None,
        "itl_mean_ms": 1e3 * sum(gaps) / len(gaps) if gaps else None,
        "itl_p80_to_p99_ms": [round(1e3 * percentile(gaps, q), 3)
                              for q in range(80, 100)] if gaps else None,
        "serve_tok_s": (prompt_tok + out_tok) / seconds,
        "queue_wait_p90_ms": (1e3 * percentile(qwait, 90)) if qwait else None,
        "n_ttft": n, "n_first_tokens": first_tokens, "n_itl": len(gaps),
        "late_p50_ms": 1e3 * percentile(late, 50) if late else None,
        "late_max_ms": 1e3 * max(late) if late else None,
        "prompt_tokens_in_window": prompt_tok,
        "output_tokens_in_window": out_tok,
        "requests": requests,
    }


def check_sample(served, seed: int, k: int):
    """Indices of the finished requests to hold against the reference: a
    seeded draw of k, the longest (prompt + output) always among them."""
    done = [j for j, sv in enumerate(served)
            if sv.req is not None and sv.req.is_done()
            and sv.req.finish_reason == "length"]
    if not done:
        return []
    longest = max(done, key=lambda j: served[j].req.prompt.size
                  + len(served[j].req.out_tokens))
    rng = np.random.default_rng([seed, 0xC0FFEE])
    rest = [j for j in done if j != longest]
    pick = list(rng.permutation(rest)[:max(0, k - 1)]) if rest else []
    return [longest] + [int(j) for j in pick]


REFERENCES = ("published", "stated")


def reference_gaps(cfg: dict, seed: int, sample, pad_to: int,
                   controls=()):
    """Run the plain reference over each sampled request's prompt with its
    served tokens, twice: ``published`` is the model's forward in full
    float32 (``highest``), ``stated`` the same forward at the products'
    precision the configuration states (``precision.matmul``; float32
    storage either way). Returns {reference: {"served": gaps of the served
    tokens, <control>: gaps of the tokens that lower precision puts
    first}}, each a flat array over all served positions of the sample."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import mixtral as ref

    weights = jax.jit(lambda k: ref.init_weights(k, cfg, 12))(
        jax.random.PRNGKey(seed))
    precision = {"published": "highest",
                 "stated": cfg["precision"]["matmul"]}

    def padded(prompt, tokens):
        """(the request as one padded sequence, the token that followed each
        position, the positions that produced the served tokens)"""
        n_p, n_o = len(prompt), len(tokens)
        seq = np.zeros(pad_to, np.int32)
        seq[:n_p] = prompt
        seq[n_p:n_p + n_o - 1] = tokens[:-1]
        following = np.zeros(pad_to, np.int32)
        following[n_p - 1:n_p - 1 + n_o] = tokens
        return seq, following, slice(n_p - 1, n_p - 1 + n_o)

    out = {r: {"served": []} for r in REFERENCES}
    kept = {r: [] for r in REFERENCES}  # each request's logits, for controls
    for prompt, tokens in sample:
        seq, following, rows = padded(prompt, tokens)
        for r in REFERENCES:
            logits = ref.forward_logits(weights, seq, cfg,
                                        precision=precision[r])
            out[r]["served"].append(np.asarray(
                ref.served_token_gaps(logits, following))[rows])
            if controls:
                kept[r].append(np.asarray(logits))  # on the host: 0.3 GB
    for c in controls:  # one at a time: each holds a second set of weights
        low_w = ref.quantize_weights(weights, c)
        for r in REFERENCES:
            out[r][c] = []
        for k, (prompt, tokens) in enumerate(sample):
            seq, _, rows = padded(prompt, tokens)
            first = jnp.argmax(ref.forward_logits(
                low_w, seq, cfg, dtype=jnp.bfloat16, precision="default"),
                axis=-1).astype(jnp.int32)
            for r in REFERENCES:
                out[r][c].append(np.asarray(ref.served_token_gaps(
                    jnp.asarray(kept[r][k]), first))[rows])
        del low_w
    return {r: {k: np.concatenate(v) if v else np.zeros(0)
                for k, v in by.items()} for r, by in out.items()}


def gap_numbers(gaps: np.ndarray, clear_gap: float) -> dict:
    """The numbers ``correct`` compares, from one set of gaps. A served
    token that is off the reference's best by no more than ``clear_gap`` lost
    a near-tie to rounding; one further off is a clear miss."""
    if gaps.size == 0:
        return {"gap_mean": float("inf"), "gap_max": float("inf"),
                "gap_p99": float("inf"), "off_best_share": 1.0,
                "clear_miss_share": 1.0, "tokens": 0}
    return {"gap_mean": float(np.mean(gaps)), "gap_max": float(np.max(gaps)),
            "gap_p99": float(np.percentile(gaps, 99)),
            "off_best_share": float(np.mean(gaps > 0)),
            "clear_miss_share": float(np.mean(gaps > clear_gap)),
            "tokens": int(gaps.size)}


def run(ctx) -> dict:
    """One run of a serving cell. Returns the run record the metric readers
    take their numbers from."""
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
    # the program's state goes before the reference's comes
    engine.close()
    del engine, backend
    for sv in served:
        sv.req = None
    jax.clear_caches()
    gc.collect()
    t_ref = time.perf_counter()
    gaps = reference_gaps(
        cfg, ctx.seed, sample,
        mix["prompt_len"]["max"] + mix["output_len"]["max"],
        controls=ctx.controls)
    clear = cfg["correct"]["clear_gap"]
    numbers = {r: gap_numbers(gaps[r]["served"], clear) for r in REFERENCES}
    control_numbers = {c: {r: gap_numbers(gaps[r][c], clear)
                           for r in REFERENCES} for c in ctx.controls}
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
        "check_tokens": numbers["published"]["tokens"],
        "check_requests": len(sample),
        "control_numbers": control_numbers,
        "memory_peak_bytes": memory_peak,
        "memory_in_use_after_window_bytes": in_use,
        "compiles_in_window": compiles_in_window,
        "steps": win["steps"], "drain_s": max(0.0, win["end_s"] - ctx.seconds),
        "decode_active": decode_active,
        "spans": rec.spans, "span_t0": rec.t0,
        "trace_path": tracer.path if tracer is not None else None,
        "trace_window_s": tracer.window_s if tracer is not None else None,
    }
