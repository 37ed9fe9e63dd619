"""Serving entry: ``python -m uccl_tpu.serve`` — the inference face of the
trainer's checkpoints.

Train → checkpoint → serve, end to end: `python -m uccl_tpu.train
--ckpt-dir d --ckpt-every k` writes orbax state whose parameter tree is
layout-identical to the serving model's, so this entry restores the params
subtree and generates through :class:`uccl_tpu.models.moe_inference.
MoEServer` — EP-sharded KV-cache prefill (sorted throughput path) +
decode (packed low-latency path, the DeepEP LL regime). The reference's
consumers reach this shape through vLLM + its transfer/EP plugins
(ep/bench/vllm/disagg_proxy.py); here it is one command:

    python -m uccl_tpu.serve --devices 8 --ckpt-dir /tmp/run1 \
        --batch 8 --prompt-len 8 --new-tokens 16

Without --ckpt-dir, params initialize from --seed (smoke/benchmark mode).
Prompts are deterministic synthetic token ids (no tokenizer in scope).

``--server`` switches from the one-shot fixed batch to the
continuous-batching engine (uccl_tpu/serving, docs/SERVING.md): a synthetic
Poisson arrival stream of mixed-length prompts flows through a FIFO
scheduler into a fixed KV slot pool, requests join and leave mid-decode,
and the summary reports TTFT/TPOT percentiles, goodput and slot occupancy.
``--check-oracle`` additionally verifies every completed request against
the one-shot ``generate`` oracle (bit-exact) and that no slot leaked — the
CI serving smoke tier:

    python -m uccl_tpu.serve --server --devices 2 --slots 2 --requests 6 \
        --prompt-len 8 --new-tokens 4 --arrival-rate 50 --check-oracle
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

if __package__ in (None, ""):
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def _load_params(ckpt_dir, step):
    """Restore the params subtree of a trainer checkpoint as HOST arrays.

    Restoring to numpy (restore_args built from the checkpoint's own
    metadata tree) decouples serving from the training topology: a
    checkpoint saved on 8 devices loads on any serving host — a plain
    restore would try to re-apply the save-time shardings and die when
    the device counts differ."""
    import numpy as np
    import orbax.checkpoint as ocp

    from uccl_tpu.train import _latest_step

    if step is None:
        step = _latest_step(ckpt_dir)
        if step is None:
            raise SystemExit(f"no step_N checkpoints in {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{step}")
    ckpt = ocp.PyTreeCheckpointer()
    meta = ckpt.metadata(path).item_metadata  # dict-shaped pytree metadata

    # walk the metadata tree by mapping structure (its leaves are metadata
    # objects that jax.tree would descend into)
    def to_args(node, as_args):
        if hasattr(node, "keys"):
            return {k: to_args(node[k], as_args) for k in node.keys()}
        if isinstance(node, (list, tuple)):
            return type(node)(to_args(v, as_args) for v in node)
        if as_args:
            return ocp.RestoreArgs(restore_type=np.ndarray)
        return 0  # placeholder leaf for the item template

    if "params" not in (meta.keys() if hasattr(meta, "keys") else ()):
        raise SystemExit(f"{path} is not a trainer checkpoint (no params)")
    # Restore ONLY the params subtree (transforms-based partial restore):
    # the optimizer moments are ~2x the param bytes and serving never
    # touches them.
    tree = ckpt.restore(
        path,
        item={"params": to_args(meta["params"], as_args=False)},
        restore_args={"params": to_args(meta["params"], as_args=True)},
        transforms={},
    )
    return tree["params"], step


def _check_sizes(params, cfg):
    """Friendly mismatch errors for EVERY size flag, before any placement:
    embed pins (vocab, dim), we_gate pins (layers, experts, ffn), wq pins
    heads*head_dim."""
    import numpy as np

    if "we_gate" not in params.get("blocks", {}):
        raise SystemExit(
            "checkpoint parameter tree has no expert weights — this looks "
            "like a dense-family checkpoint; serve routes families via the "
            "checkpoint dir's config.json (re-save with the current trainer "
            "or restore it manually)"
        )
    checks = [
        ("embed", (cfg.vocab, cfg.dim), "--vocab/--dim"),
        ("blocks.we_gate",
         (cfg.n_layers, cfg.moe_experts, cfg.dim, cfg.moe_ffn),
         "--layers/--experts/--dim/--ffn"),
        ("blocks.wq",
         (cfg.n_layers, cfg.dim, cfg.n_heads * cfg.head_dim),
         "--layers/--dim/--heads"),
    ]
    for name, want, flags in checks:
        leaf = params
        for part in name.split("."):
            leaf = leaf[part]
        got = tuple(np.shape(leaf))
        if got != want:
            raise SystemExit(
                f"checkpoint {name} {got} != model {want} ({flags}): "
                "pass the training run's size flags"
            )


def _timed_windows(run_full, run_one, batch, new_tokens, reps):
    """Measure the one-shot serving windows ``reps`` times; returns
    (last full-window output, last full-window seconds, extra summary).

    The 1-token window IS the TTFT window (prompt → first token), and the
    per-rep delta (full − one)/(N−1) is the decode-step window — prefill
    and the fixed dispatch cost cancel in the delta (the honest-decode
    rationale below). Percentile definitions are shared with the
    continuous-batching engine (uccl_tpu/serving/metrics.py). Callers must
    have warmed BOTH programs; ``run_one`` is None when N == 1 (the full
    window then doubles as the TTFT window)."""
    from uccl_tpu import obs
    from uccl_tpu.serving.metrics import percentile, percentiles_ms

    ttft, steps, fulls = [], [], []
    out = None
    for _ in range(max(1, reps)):
        if run_one is not None:
            t0 = time.perf_counter()
            with obs.span("serve.ttft_window", track="serve"):
                run_one()
            ttft.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        with obs.span("serve.full_window", track="serve",
                      new_tokens=new_tokens):
            out = run_full()
        fulls.append(time.perf_counter() - t0)
        if run_one is not None and fulls[-1] > ttft[-1]:
            steps.append((fulls[-1] - ttft[-1]) / (new_tokens - 1))
    if run_one is None:
        ttft = list(fulls)
    extra = {"ttft_ms": percentiles_ms(ttft)}
    if steps:
        extra["decode_step_ms"] = percentiles_ms(steps)
        # the delta metric over the MEDIAN windows — only when positive,
        # never a clamped absurdity (see the window notes below)
        med_one, med_full = percentile(ttft, 50), percentile(fulls, 50)
        if med_full > med_one:
            extra["decode_tokens_per_sec"] = round(
                batch * (new_tokens - 1) / (med_full - med_one), 1
            )
    return out, fulls[-1], extra


def _params_dtype(params) -> str:
    import jax

    # the embedding's: the storage dtype of the matrices (a tree's first
    # leaf may be a norm, which stays float32 under bfloat16 weights)
    leaf = params["embed"] if "embed" in params else jax.tree.leaves(params)[0]
    return str(leaf.dtype)


def _moe_cfg(args):
    """The MoE stack's model description: the published keys of a Hugging
    Face ``config.json`` (``--model-config``: any family
    ``MoEServeConfig.from_hf`` reads, weights stored in its
    ``torch_dtype``), sized for this process's widest write and a drop-free
    wire; or else the hand-sized flags (the uniform block)."""
    from uccl_tpu.models.moe_inference import MoEServeConfig

    if not args.model_config:
        return MoEServeConfig(
            vocab=args.vocab, dim=args.dim, n_layers=args.layers,
            n_heads=args.heads, n_kv_heads=args.kv_heads,
            head_dim=args.dim // args.heads, moe_experts=args.experts,
            moe_ffn=args.ffn,
        )
    if args.ckpt_dir:
        raise SystemExit("--model-config serves seeded weights; it takes "
                         "no --ckpt-dir")
    with open(args.model_config) as f:
        hf = json.load(f)
    cfg = MoEServeConfig.from_hf(
        hf, param_dtype=hf.get("torch_dtype", "float32"))
    return cfg.sized_for_serving(max(args.prefill_chunk, args.spec_k + 1))


def _moe_paths(cfg, impl, world, params):
    """The MoE serving paths as resolved on this backend, for the summary:
    prefill always takes the sorted EP path; ``impl`` is the decode step's.
    Under "ll" the wire is ragged only where the backend lowers
    ``lax.ragged_all_to_all`` — named, so a run says which one it was."""
    from uccl_tpu.ep.ll import wire_supports_ragged

    out = {"dtype": _params_dtype(params), "devices_used": world,
           "prefill_impl": "sort", "decode_impl": impl,
           "moe_wire": cfg.moe_wire, "attn": cfg.attn, "gate": cfg.gate}
    if impl == "ll":
        out["ll_wire"] = "ragged" if wire_supports_ragged() else "dense"
    return out


def _serve_continuous(args, saved_cfg):
    """--server: the continuous-batching engine under Poisson arrivals.

    Mixed-length synthetic prompts arrive at --arrival-rate req/s, flow
    through the FIFO scheduler into a --slots KV slot pool, and decode in
    one masked batch; the summary line is the engine's metrics snapshot
    (TTFT/TPOT percentiles, goodput, occupancy — docs/SERVING.md).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from uccl_tpu import obs
    from uccl_tpu.serving import (
        AdapterStore, DenseBackend, MoEBackend, Router, SamplingParams,
        ServingEngine, ServingMetrics, make_lora, materialize,
        replicate_backend,
    )
    from uccl_tpu.serving.loadgen import (
        assign_classes, drive, synth_workload, warm_engine, warm_replicas,
    )
    from uccl_tpu.utils import device

    stack = args.stack
    if stack == "auto":
        stack = ("dense" if saved_cfg is not None
                 and saved_cfg.get("model") == "dense" else "moe")
    if args.slots < 1:
        raise SystemExit(f"--slots must be >= 1, got {args.slots}")
    if args.spec_k < 0:
        raise SystemExit(f"--spec-k must be >= 0, got {args.spec_k}")
    if args.replicas < 1:
        raise SystemExit(f"--replicas must be >= 1, got {args.replicas}")
    if not (0.0 <= args.interactive_frac <= 1.0):
        raise SystemExit("--interactive-frac must be in [0, 1]")
    if args.temperature < 0:
        raise SystemExit(f"--temperature must be >= 0, got "
                         f"{args.temperature}")
    if not (0.0 < args.top_p <= 1.0):
        raise SystemExit(f"--top-p must be in (0, 1], got {args.top_p}")
    if args.top_k < 0:
        raise SystemExit(f"--top-k must be >= 0, got {args.top_k}")
    if args.tenants < 0:
        raise SystemExit(f"--tenants must be >= 0, got {args.tenants}")
    if args.tenants and args.priority_classes:
        raise SystemExit("--tenants and --priority-classes are mutually "
                         "exclusive admission policies (per-tenant DRR "
                         "has no class ladder)")
    if args.adapter_rank < 0:
        raise SystemExit(f"--adapter-rank must be >= 0, got "
                         f"{args.adapter_rank}")
    if args.adapter_rank and not args.tenants:
        raise SystemExit("--adapter-rank needs --tenants (adapters are "
                         "per-tenant)")
    if args.step_tokens and not args.prefill_chunk:
        raise SystemExit("--step-tokens needs --prefill-chunk (the "
                         "whole-prompt path has no sub-step unit to budget)")
    if args.prefill_chunk and args.step_tokens \
            and args.step_tokens < args.prefill_chunk:
        raise SystemExit(
            f"--step-tokens {args.step_tokens} must be >= --prefill-chunk "
            f"{args.prefill_chunk}, or no request could ever be admitted"
        )
    max_seq = args.max_seq or (args.prompt_len + args.new_tokens)
    if args.prompt_len + args.new_tokens > max_seq:
        raise SystemExit("--prompt-len + --new-tokens exceed --max-seq")

    # per-tenant LoRA adapters: one published adapter per synthetic
    # tenant; the engine fuses them as batched per-slot deltas and the
    # oracle re-derives each request from dense-materialized W+BA params
    head_dim = args.dim // args.heads
    store = None
    lora_trees = {}
    if args.adapter_rank:
        store = AdapterStore(
            args.layers, args.dim, args.heads * head_dim,
            args.kv_heads * head_dim, max_rank=args.adapter_rank,
            capacity=max(4, args.slots),
        )
        for j in range(args.tenants):
            tree = make_lora(
                jax.random.PRNGKey(args.seed * 7919 + j + 1), args.layers,
                args.dim, args.heads * head_dim,
                args.kv_heads * head_dim, args.adapter_rank,
            )
            lora_trees[f"t{j}"] = tree
            store.publish(f"t{j}", tree)

    step = None
    world = 1
    if stack == "dense":
        from uccl_tpu.models.dense import DenseConfig, init_params
        from uccl_tpu.models.inference import generate

        dcfg = DenseConfig(
            vocab=args.vocab, dim=args.dim, n_layers=args.layers,
            n_heads=args.heads, n_kv_heads=args.kv_heads,
            head_dim=args.dim // args.heads, ffn=args.ffn,
        )
        if args.ckpt_dir:
            params, step = _load_params(args.ckpt_dir, args.step)
            params = jax.tree.map(
                lambda x: jnp.asarray(x, jnp.float32), params
            )
            print(f"serving {args.ckpt_dir}/step_{step} (dense)", flush=True)
        else:
            params = init_params(jax.random.PRNGKey(args.seed), dcfg)
        backend = DenseBackend(params, dcfg, n_slots=args.slots,
                               max_seq=max_seq)
        vocab = dcfg.vocab
        # no mesh: the dense stack runs on one device whatever the host has
        paths = {"dtype": _params_dtype(params), "devices_used": 1}

        mat_params = {}

        def oracle(req):
            # adapted requests verify against dense-materialized W+BA
            # params (cached per adapter) — the fused-delta exactness bar
            p = params
            if req.adapter is not None:
                if req.adapter not in mat_params:
                    mat_params[req.adapter] = materialize(
                        params, lora_trees[req.adapter]
                    )
                p = mat_params[req.adapter]
            toks = generate(
                p, jnp.asarray(req.prompt)[None], dcfg,
                max_new_tokens=req.max_new_tokens, max_seq=max_seq,
                sampling=req.sampling,
            )
            return np.asarray(toks)[0, : req.n_generated]
    else:
        from uccl_tpu.models.moe_inference import (
            MoEServeConfig, MoEServer, init_params,
        )
        from uccl_tpu.parallel.mesh import MeshConfig, make_mesh

        cfg = _moe_cfg(args)
        n = len(jax.devices())
        world = args.dp or n
        if world > n:
            raise SystemExit(
                f"--dp {world} exceeds the {n} available device(s)"
            )
        if args.slots % world:
            raise SystemExit(
                f"--slots {args.slots} must divide by the serving world "
                f"{world} (one slot pool row per shard batch row)"
            )
        impl = args.impl if args.impl != "auto" else (
            "sort" if world == 1 else "ll"
        )
        mesh = make_mesh(MeshConfig(dp=world), jax.devices()[:world])
        server = MoEServer(cfg, mesh)
        if args.ckpt_dir:
            params, step = _load_params(args.ckpt_dir, args.step)
            _check_sizes(params, cfg)
            params = jax.tree.map(
                lambda x: jnp.asarray(x, jnp.float32), params
            )
            print(f"serving {args.ckpt_dir}/step_{step}", flush=True)
        else:
            params = init_params(jax.random.PRNGKey(args.seed), cfg)
        backend = MoEBackend(server, server.shard_params(params),
                             batch_local=args.slots // world,
                             max_seq=max_seq, decode_impl=impl)
        vocab = cfg.vocab
        paths = _moe_paths(cfg, impl, world, params)

        oracle_srv = {}

        def oracle(req):
            # one-shot generate on a world-1 mesh: sharding is
            # semantics-free (the tested parity property), so the 1-shard
            # program is the cheapest exact oracle. Built once — its _fns
            # cache then makes per-request calls pure cache hits. Adapted
            # requests verify against dense-materialized W+BA params,
            # sharded once per adapter.
            if "srv" not in oracle_srv:
                srv1 = MoEServer(cfg, make_mesh(MeshConfig(dp=1),
                                                jax.devices()[:1]))
                oracle_srv["srv"] = srv1
                oracle_srv[None] = srv1.shard_params(params)
            srv1 = oracle_srv["srv"]
            if req.adapter not in oracle_srv:
                oracle_srv[req.adapter] = srv1.shard_params(
                    materialize(params, lora_trees[req.adapter])
                )
            toks = srv1.generate(
                oracle_srv[req.adapter],
                jnp.asarray(req.prompt)[None, None],
                req.max_new_tokens, max_seq, impl=impl,
                sampling=req.sampling,
            )
            return np.asarray(toks)[0, 0, : req.n_generated]

    # preemption rides the priority flag whenever the engine is chunked
    # (chunk boundaries are what make pause/resume nearly free); a
    # whole-prompt priority engine still class-orders its queue
    preempt = bool(args.priority_classes and args.prefill_chunk)
    engines = [ServingEngine(
        b, max_queue=args.max_queue or None, register_stats=True,
        prefill_chunk=args.prefill_chunk or None,
        step_tokens=args.step_tokens or None,
        spec_k=args.spec_k or None,
        priority_classes=args.priority_classes, preempt=preempt,
        adapters=store, tenant_fair=bool(args.tenants) or None,
    ) for b in replicate_backend(backend, args.replicas)]
    target = engines[0] if args.replicas == 1 else Router(engines)

    # synthetic workload (mixed prompt lengths, Poisson arrivals), compile
    # warmup, and the wall-clock drive loop — shared with
    # benchmarks/serving_bench.py (uccl_tpu/serving/loadgen.py)
    rng = np.random.default_rng(args.seed)
    prompts, lens, arrivals = synth_workload(
        rng, args.requests, args.prompt_len, vocab, args.arrival_rate
    )
    # classes AFTER arrivals: the mix knob never perturbs arrival timing
    priorities = (assign_classes(rng, args.requests, args.interactive_frac,
                                 pattern=args.class_pattern)
                  if args.priority_classes else None)
    # tenants round-robin the arrival order; per-request seeds are
    # --seed + i (lockstep counter keys keep --check-oracle bit-exact)
    tenant_labels = ([f"t{i % args.tenants}" for i in range(args.requests)]
                     if args.tenants else None)
    adapter_labels = (list(tenant_labels) if args.adapter_rank else None)
    samplings = None
    if args.temperature > 0:
        samplings = [
            SamplingParams(temperature=args.temperature, top_p=args.top_p,
                           top_k=args.top_k, seed=args.seed + i)
            for i in range(args.requests)
        ]
    t_warm = time.perf_counter()
    if args.replicas == 1:
        warm_engine(target, lens, max_seq, args.new_tokens)
    else:
        warm_replicas(target, lens, max_seq, args.new_tokens)
    warmup_s = time.perf_counter() - t_warm
    # warm-up claims to compile every program the run will use: count what
    # still compiles afterwards, inside the measured window
    compiles_warm = device.compile_counts()
    metrics_srv = None
    if args.metrics_port:
        # live /metrics (Prometheus text) + /snapshot (JSON) for the run's
        # duration — each scrape appends the engine's current percentile
        # lines to the registry dump
        metrics_srv = obs.MetricsServer(
            args.metrics_port,
            extra_lines_fn=lambda: ServingMetrics.prometheus_lines(
                target.snapshot()
            ),
        )
        print(f"metrics: http://127.0.0.1:{metrics_srv.port}/metrics "
              f"(+ /snapshot)", flush=True)
    try:
        reqs, wall = drive(target, prompts, arrivals, args.new_tokens,
                           priorities=priorities, tenants=tenant_labels,
                           samplings=samplings, adapters=adapter_labels)
    finally:
        if metrics_srv is not None:
            metrics_srv.close()

    compiles_after_warmup = device.counts_since(compiles_warm)["compiles"]
    snap = target.snapshot()
    target.close()
    # histogram-derived TTFT percentiles beside the sample-derived ones
    # (snap["ttft_ms"]): warmup reset both, so the two derivations cover
    # the same observations and must agree within one bucket width — the
    # recorded cross-check for the merge-safe fleet path
    # (docs/OBSERVABILITY.md)
    from uccl_tpu.serving.metrics import TTFT_HIST

    ttft_hist_ms = {
        f"p{q}": round(v * 1e3, 3) for q in (50, 95)
        for v in [TTFT_HIST.quantile(q)] if v is not None
    }
    written = obs.dump_from_args(
        args, extra_lines=ServingMetrics.prometheus_lines(snap)
    )
    for path in written:
        print(f"wrote {path}", flush=True)
    summary = {
        "mode": "serve-continuous", "schema_version": obs.SCHEMA_VERSION,
        "device": device.describe(),
        "stack": stack, "ckpt_step": step, **paths,
        "warmup_s": round(warmup_s, 3),
        "compiles_after_warmup": compiles_after_warmup,
        "peak_bytes_in_use": device.peak_bytes_in_use(),
        "world": world, "slots": args.slots, "requests": args.requests,
        "arrival_rate": args.arrival_rate, "new_tokens": args.new_tokens,
        "prefill_chunk": args.prefill_chunk or None,
        "step_tokens": args.step_tokens or None,
        "spec_k": args.spec_k or None,
        "replicas": args.replicas,
        "priority_classes": bool(args.priority_classes),
        "preempt": preempt,
        "interactive_frac": (args.interactive_frac
                             if args.priority_classes else None),
        "temperature": args.temperature or None,
        "top_p": args.top_p if args.temperature else None,
        "top_k": args.top_k if args.temperature else None,
        "tenants": args.tenants or None,
        "adapter_rank": args.adapter_rank or None,
        "wall_s": round(wall, 3), "ttft_hist_ms": ttft_hist_ms, **snap,
    }
    if reqs:
        print(f"first request: {reqs[0].out_tokens}", flush=True)
    # the drained engine's books, in every summary: slots still occupied
    # and requests that ended short of their token budget (the synthetic
    # stream has no EOS, so both must be 0)
    leaked = (target.leaked() if args.replicas > 1
              else target.pool.leaked())
    summary["leaked_slots"] = leaked
    summary["short_requests"] = sum(
        r.n_generated != r.max_new_tokens for r in reqs
    )

    if args.check_oracle:
        qsize = (target.qsize if args.replicas > 1
                 else target.sched.qsize)
        mismatched = []
        for r in reqs:
            want = oracle(r)
            if r.out_tokens != want.tolist():
                mismatched.append((r.rid, r.out_tokens, want.tolist()))
        ok = (not leaked and not mismatched and qsize == 0
              and snap["completed"] == len(reqs))
        summary["oracle_match"] = bool(ok)
        print(json.dumps(summary), flush=True)
        if not ok:
            for rid, got, want in mismatched:
                print(f"request {rid}: got {got} want {want}",
                      file=sys.stderr)
            raise SystemExit(
                f"oracle check FAILED: leaked={leaked} "
                f"mismatched={len(mismatched)}"
            )
        print(f"oracle check: {len(reqs)} requests bit-exact, "
              f"0 leaked slots", flush=True)
    else:
        print(json.dumps(summary), flush=True)
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m uccl_tpu.serve")
    ap.add_argument("--devices", type=int, default=0,
                    help="force an N-device virtual CPU mesh (tests/dev)")
    ap.add_argument("--dp", type=int, default=0,
                    help="serving world (default: all devices)")
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=0,
                    help="KV capacity (default: prompt+new)")
    ap.add_argument("--impl", default="auto", choices=["auto", "ll", "sort"],
                    help="decode-step EP path (prefill always uses sort). "
                         "'auto' follows the measurements: sort at world 1 "
                         "(wins 1.2-3.2x at every batch, PERF.md), ll on "
                         "multi-member worlds where its packed rows cut "
                         "actual wire bytes (the DeepEP LL regime)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timing-reps", type=int, default=3,
                    help="one-shot mode: repetitions of the timing windows "
                         "feeding the TTFT/decode-step p50/p95 percentiles")
    # continuous-batching server mode (uccl_tpu/serving, docs/SERVING.md)
    ap.add_argument("--server", action="store_true",
                    help="continuous-batching engine under a synthetic "
                         "Poisson arrival stream (vs the one-shot batch)")
    ap.add_argument("--slots", type=int, default=4,
                    help="server: KV slot pool size (MoE: must divide by "
                         "the serving world; B_loc = slots/world)")
    ap.add_argument("--requests", type=int, default=16,
                    help="server: number of synthetic requests")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    help="server: Poisson arrival rate in req/s "
                         "(0 = all arrive at t=0)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="server: bounded queue depth; submissions beyond "
                         "it are rejected (backpressure). 0 = unbounded")
    ap.add_argument("--stack", default="auto",
                    choices=["auto", "dense", "moe"],
                    help="server: model stack ('auto': dense for dense "
                         "checkpoints, else MoE)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="server: chunked prefill — admitted prompts "
                         "prefill C tokens per engine step so in-flight "
                         "decodes never stall behind more than one chunk "
                         "(one compiled prefill program instead of pow2 "
                         "buckets). 0 = whole-prompt prefill")
    ap.add_argument("--step-tokens", type=int, default=0,
                    help="server: per-step token budget (decoding slot = 1 "
                         "token, or 1+K under --spec-k — the verify window "
                         "really runs K+1 rows; prefill chunk = C); "
                         "admission defers while the step's committed "
                         "spend would exceed it. Needs --prefill-chunk. "
                         "0 = unbudgeted")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="server: speculative decoding — the prompt-lookup "
                         "NGram drafter proposes K tokens per decoding "
                         "slot each step, one batched [slots, K+1] verify "
                         "commits each slot's accepted prefix + 1 "
                         "target token (bit-identical to vanilla greedy "
                         "decode, docs/SERVING.md). 0 = off")
    ap.add_argument("--replicas", type=int, default=1,
                    help="server: engine replica count behind the "
                         "least-loaded router (each replica owns a "
                         "--slots KV pool; admission steers by live "
                         "free-slot/token-debt/queue-wait signals, "
                         "docs/SERVING.md)")
    ap.add_argument("--priority-classes", action="store_true",
                    help="server: SLO classes — each request is "
                         "'interactive' (admits first; with "
                         "--prefill-chunk it preempts running batch work "
                         "at chunk boundaries, bit-exact resume) or "
                         "'batch', drawn per request at "
                         "--interactive-frac")
    ap.add_argument("--interactive-frac", type=float, default=0.5,
                    help="server: fraction of requests in the "
                         "interactive class under --priority-classes")
    ap.add_argument("--class-pattern", default="bernoulli",
                    choices=["bernoulli", "batch-first"],
                    help="server: how classes map onto the arrival "
                         "order — 'bernoulli' interleaves (realistic "
                         "mixed traffic), 'batch-first' front-loads all "
                         "batch work so every interactive arrival finds "
                         "the slots occupied (the deterministic "
                         "preemption smoke fixture)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="server: stochastic sampling temperature "
                         "(0 = greedy). Request i samples under "
                         "per-request seed --seed+i with lockstep "
                         "counter-based keys, so --check-oracle stays "
                         "bit-exact against the SAMPLED one-shot "
                         "generate oracle at the same seed")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="server: nucleus sampling mass in (0, 1] "
                         "(active with --temperature > 0)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="server: top-k truncation, 0 = off (active "
                         "with --temperature > 0)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="server: N synthetic tenants round-robin over "
                         "the arrival stream, admitted via per-tenant "
                         "deficit round-robin (TenantFairScheduler); "
                         "metrics gain tenant= labeled series. 0 = one "
                         "implicit tenant, plain FIFO")
    ap.add_argument("--adapter-rank", type=int, default=0,
                    help="server: per-tenant LoRA adapters of this rank "
                         "(needs --tenants), applied as batched fused "
                         "per-slot deltas; --check-oracle verifies "
                         "against dense-materialized W+BA params. "
                         "0 = no adapters")
    ap.add_argument("--check-oracle", action="store_true",
                    help="server: verify every completed request is "
                         "bit-identical to the one-shot generate oracle "
                         "and that no KV slot leaked (CI smoke tier)")
    # model size — must match the checkpoint when --ckpt-dir is given
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--ffn", type=int, default=128)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--model-config", default="",
                    help="MoE stack: a Hugging Face config.json (mixtral or "
                         "glm4_moe_lite/deepseek_v3 keys) to build the model "
                         "description from, instead of the size flags; "
                         "seeded weights in its torch_dtype")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--step", type=int, default=None,
                    help="checkpoint step (default: latest)")
    # observability surfaces (docs/OBSERVABILITY.md): --trace-out enables
    # the event tracer and writes a Chrome-trace/Perfetto JSON at exit;
    # --metrics-out dumps the Prometheus-text registry; --metrics-port
    # serves live /metrics + /snapshot during --server runs
    from uccl_tpu import obs

    obs.add_cli_args(ap)
    args = ap.parse_args(argv)
    obs.setup_from_args(args)
    # crash-safety net: a run that dies mid-flight still dumps its partial
    # trace/metrics (the explicit dumps below win when they run)
    obs.dump_at_exit(args)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    else:
        import jax

    import jax.numpy as jnp
    import numpy as np

    from uccl_tpu.utils import device

    device.enable_compile_cache()

    from uccl_tpu.models.moe_inference import (
        MoEServeConfig, MoEServer, init_params,
    )
    from uccl_tpu.parallel.mesh import MeshConfig, make_mesh

    # A trainer checkpoint records its model family + sizes in config.json;
    # prefer that over flags (flags that DIFFER are an error — shapes like
    # heads vs kv-heads cannot all be recovered from param shapes alone,
    # so silent flag drift would serve silently-wrong tokens).
    saved_cfg = None
    if args.ckpt_dir:
        cfg_path = os.path.join(args.ckpt_dir, "config.json")
        if os.path.exists(cfg_path):
            with open(cfg_path) as f:
                saved_cfg = json.load(f)
            if saved_cfg.get("model") not in ("flagship", "dense"):
                raise SystemExit(
                    f"{args.ckpt_dir} holds a {saved_cfg.get('model')!r} "
                    "checkpoint; serve handles flagship (MoE) and dense"
                )
            defaults = ap.parse_args([])
            pairs = [
                ("vocab", "vocab"), ("dim", "dim"), ("layers", "layers"),
                ("heads", "heads"), ("kv_heads", "kv_heads"),
                ("ffn", "ffn"),
            ]
            if saved_cfg.get("model") == "flagship":
                pairs.append(("experts", "experts"))  # MoE-only flag
            for flag, key in pairs:
                given = getattr(args, flag)
                if given != getattr(defaults, flag) and given != saved_cfg[key]:
                    raise SystemExit(
                        f"--{flag.replace('_', '-')} {given} != checkpoint "
                        f"config {saved_cfg[key]} ({cfg_path})"
                    )
                setattr(args, flag, saved_cfg[key])
    if args.server:
        return _serve_continuous(args, saved_cfg)
    if saved_cfg is not None and saved_cfg.get("model") == "dense":
        # Dense (Llama-family) checkpoints generate through the cached
        # single-shard KV path (models/inference.py) — no EP mesh.
        from uccl_tpu.models.dense import DenseConfig
        from uccl_tpu.models.inference import generate

        dcfg = DenseConfig(
            vocab=args.vocab, dim=args.dim, n_layers=args.layers,
            n_heads=args.heads, n_kv_heads=args.kv_heads,
            head_dim=args.dim // args.heads, ffn=args.ffn,
        )
        max_seq = args.max_seq or (args.prompt_len + args.new_tokens)
        if args.prompt_len + args.new_tokens > max_seq:
            raise SystemExit("--prompt-len + --new-tokens exceed --max-seq")
        params, step = _load_params(args.ckpt_dir, args.step)
        params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
        print(f"serving {args.ckpt_dir}/step_{step} (dense)", flush=True)
        rng = np.random.default_rng(args.seed)
        prompt = jnp.asarray(
            rng.integers(0, dcfg.vocab, (args.batch, args.prompt_len)),
            jnp.int32,
        )
        # One jitted program (prefill + decode scan), cached per shape in
        # inference.generate — the warmup call at the SAME new_tokens
        # compiles it; the timed call is a pure cache hit.
        # host-read the warmup: the call itself is async and compile can
        # complete with the execution still queued — an unread warmup
        # leaks its execution into the timed window
        np.asarray(generate(params, prompt, dcfg,
                            max_new_tokens=args.new_tokens,
                            max_seq=max_seq))
        # Honest decode throughput: the full timed window INCLUDES prefill,
        # so dividing by batch*new_tokens alone would flatter short windows.
        # A second program at 1 new token (warmed the same way) gives the
        # TTFT window, and the window delta is decode-only time for
        # new_tokens-1 tokens. Repeated reps feed the p50/p95 percentiles
        # (serving/metrics.py definitions).
        run_one = None
        if args.new_tokens > 1:
            np.asarray(generate(params, prompt, dcfg, max_new_tokens=1,
                                max_seq=max_seq))
            run_one = lambda: np.asarray(generate(  # noqa: E731
                params, prompt, dcfg, max_new_tokens=1, max_seq=max_seq))
        run_full = lambda: np.asarray(generate(  # noqa: E731
            params, prompt, dcfg, max_new_tokens=args.new_tokens,
            max_seq=max_seq))
        out, dt, extra = _timed_windows(
            run_full, run_one, args.batch, args.new_tokens, args.timing_reps
        )
        summary = {
            "mode": "serve", "schema_version": obs.SCHEMA_VERSION,
            "device": device.describe(),
            "ckpt_step": step, "impl": "dense",
            "dtype": _params_dtype(params), "devices_used": 1,
            "world": 1, "batch": args.batch,
            "new_tokens": args.new_tokens,
            # the raw window metric, kept under an honest name: it spans
            # prefill AND decode
            "window": "prefill+decode",
            "tokens_per_sec": round(args.batch * args.new_tokens / dt, 1),
            **extra,
        }
        print(f"first sequence: {out[0].tolist()}", flush=True)
        print(json.dumps(summary), flush=True)
        obs.dump_from_args(args)
        return summary

    cfg = _moe_cfg(args)
    n = len(jax.devices())
    world = args.dp or n
    # fail the cheap flag checks in milliseconds, BEFORE any restore work
    if world > n:
        raise SystemExit(f"--dp {world} exceeds the {n} available device(s)")
    if args.batch % world:
        raise SystemExit(f"--batch {args.batch} must divide by world {world}")
    max_seq = args.max_seq or (args.prompt_len + args.new_tokens)
    if args.prompt_len + args.new_tokens > max_seq:
        raise SystemExit(
            f"--prompt-len {args.prompt_len} + --new-tokens "
            f"{args.new_tokens} exceed --max-seq {max_seq}"
        )
    # '--impl auto' follows the measurements (PERF.md round-5 decode table):
    # at world 1 the sorted path wins 1.2-3.2x at every batch — LL's packed
    # rows save WIRE bytes, which a single-member world never moves. Multi-
    # member worlds keep the DeepEP LL decode regime. Explicit --impl wins.
    impl = args.impl if args.impl != "auto" else (
        "sort" if world == 1 else "ll"
    )
    mesh = make_mesh(MeshConfig(dp=world), jax.devices()[:world])
    server = MoEServer(cfg, mesh)

    step = None
    if args.ckpt_dir:
        params, step = _load_params(args.ckpt_dir, args.step)
        _check_sizes(params, cfg)
        params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
        print(f"serving {args.ckpt_dir}/step_{step}", flush=True)
    else:
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
    placed = server.shard_params(params)

    b_local = args.batch // world
    rng = np.random.default_rng(args.seed)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab, (world, b_local, args.prompt_len)),
        jnp.int32,
    )

    # Warmup compiles the prefill + decode-scan programs. It must use the
    # SAME new_tokens as the timed run: generate's decode loop is one
    # jitted lax.scan whose length is baked into the program, so a
    # 1-token warmup would compile a different scan and the timed call
    # would pay the real compile. Host-READ the result: the call is
    # async, and an unread warmup leaks its execution into the timed
    # window (see the dense branch note).
    np.asarray(server.generate(
        placed, prompt, args.new_tokens, max_seq, impl=impl
    ))
    # decode-only throughput via the 1-token delta (see the dense branch:
    # the timed window spans prefill+decode, so the delta of two windows
    # is the honest decode number); repeated reps feed the TTFT /
    # decode-step percentiles
    run_one = None
    if args.new_tokens > 1:
        np.asarray(server.generate(placed, prompt, 1, max_seq, impl=impl))
        run_one = lambda: np.asarray(server.generate(  # noqa: E731
            placed, prompt, 1, max_seq, impl=impl))
    run_full = lambda: np.asarray(server.generate(  # noqa: E731
        placed, prompt, args.new_tokens, max_seq, impl=impl))
    out, dt, extra = _timed_windows(
        run_full, run_one, args.batch, args.new_tokens, args.timing_reps
    )
    total = args.batch * args.new_tokens
    summary = {
        "mode": "serve",
        "schema_version": obs.SCHEMA_VERSION,
        "device": device.describe(),
        "ckpt_step": step,
        "impl": impl,
        **_moe_paths(cfg, impl, world, params),
        "world": world,
        "batch": args.batch,
        "new_tokens": args.new_tokens,
        "window": "prefill+decode",
        "tokens_per_sec": round(total / dt, 1),
        **extra,
    }
    print(f"first sequence: {out[0, 0].tolist()}", flush=True)
    print(json.dumps(summary), flush=True)
    obs.dump_from_args(args)
    return summary


if __name__ == "__main__":
    main()
