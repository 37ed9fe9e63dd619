"""Disaggregated prefill → decode serving with KV-cache transfer over P2P.

The analog of the reference's prefill/decode disaggregation workload
(ep/bench/vllm/disagg_proxy.py; "KV cache transfer" README.md:18), in two
tiers:

* **Default — chunk-streamed serving** (`uccl_tpu/serving/disagg.py`): a
  PrefillWorker engine (chunked prefill + prefix-reuse cache) streams each
  request's KV slabs chunk-by-chunk into a DecodeWorker process over the
  one-sided write path as they are computed; the decode engine adopts each
  request and continues generation. Three requests share a system-prompt
  prefix, so the run demonstrates ≥1 prefix-cache hit (tokens reused, not
  recomputed — the counters prove it) AND bit-exact output.
* **Legacy one-shot handoff** (`--compress` / `--elastic` / `--one-shot`):
  the original whole-cache advertise → write → notif flow, kept for the
  compressed-wire (DietGPU-style) and elastic-KV demos.

Either way the script asserts the disaggregated output matches
single-worker generation exactly (fp8 is lossy: agreement-checked) and
exits non-zero on mismatch — tests/test_disagg_kv.py pins that contract.

Usage: python examples/disagg_kv.py [--new-tokens 12] [--metrics-out M]
"""

from __future__ import annotations

import argparse
import multiprocessing as mp
import os
import time
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def _pin_cpu():
    """This example moves KV over the host p2p wire between two OS
    processes: parent and worker are pinned to the CPU backend, so neither
    reaches for a chip the other would need."""
    from uccl_tpu.utils.device import pin_cpu

    pin_cpu()


CFG_KW = dict(
    vocab=128, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16, ffn=128
)
MAX_SEQ = 64
PROMPT_LEN = 8
BATCH = 2
STREAM_CHUNK = 4  # prefill chunk = KV stream granularity = prefix-trie key
STREAM_PROMPT_LEN = 12  # 3 chunks; requests share the first 8 tokens
STREAM_REQUESTS = 3


def _make(seed=0):
    import jax

    from uccl_tpu.models.dense import DenseConfig, init_params

    cfg = DenseConfig(**CFG_KW)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def _prompt():
    import numpy as np

    return np.random.default_rng(7).integers(0, 128, (BATCH, PROMPT_LEN)).astype(
        np.int32
    )


# -- default: chunk-streamed disaggregated serving --------------------------
def _role_path(path: str, role: str) -> str:
    """Per-role artifact path: ``/tmp/t.json`` -> ``/tmp/t.decode.json``.
    The fleet smoke arm (qa.sh / ci.yml) merges the two roles' traces
    with scripts/trace_merge.py and federates the two metrics files with
    ``python -m uccl_tpu.obs.aggregate``."""
    stem, ext = os.path.splitext(path)
    return f"{stem}.{role}{ext or '.json'}"


def stream_decode_worker(port_q, result_q, n_requests, trace_out="",
                         metrics_out=""):
    """Decode-fleet process: advertises its slot-pool KV mirror, grants
    incoming streams, adopts + decodes each request, reports the outputs
    and its engine snapshot (with the disagg TTFT split). With
    ``trace_out``/``metrics_out`` it dumps its OWN role-labeled
    observability artifacts — the decode half of the fleet trace (its
    clock metadata carries the offset the HELLO exchange estimated)."""
    _pin_cpu()
    import numpy as np

    from uccl_tpu import obs
    from uccl_tpu.p2p import Endpoint
    from uccl_tpu.serving import DenseBackend, ServingEngine, ServingMetrics
    from uccl_tpu.serving.disagg import DecodeWorker

    if trace_out:
        obs.enable_tracing()
    cfg, params = _make()
    backend = DenseBackend(params, cfg, n_slots=2, max_seq=MAX_SEQ)
    engine = ServingEngine(backend)
    ep = Endpoint()
    port_q.put(ep.port)
    dw = DecodeWorker(engine, ep)
    dw.attach()
    done = dw.serve(n_requests, timeout_s=180.0)
    snap = engine.snapshot()
    if trace_out:
        obs.write_trace(trace_out, process_name="uccl_tpu.decode")
    if metrics_out:
        obs.write_metrics(
            metrics_out,
            extra_lines=ServingMetrics.prometheus_lines(snap),
        )
    result_q.put((
        [(np.asarray(r.prompt), list(r.out_tokens), int(r.cache_hit_len))
         for r in done],
        snap,
    ))
    ep.close()


def _stream_main(args) -> int:
    import jax.numpy as jnp
    import numpy as np

    from uccl_tpu import obs
    from uccl_tpu.models.inference import generate
    from uccl_tpu.p2p import Endpoint
    from uccl_tpu.serving import (
        DenseBackend, PrefixCache, ServingEngine, ServingMetrics,
    )
    from uccl_tpu.serving.disagg import PrefillWorker

    ctx = mp.get_context("spawn")
    port_q, result_q = ctx.Queue(), ctx.Queue()
    worker = ctx.Process(
        target=stream_decode_worker,
        args=(port_q, result_q, STREAM_REQUESTS,
              _role_path(args.trace_out, "decode") if args.trace_out
              else "",
              _role_path(args.metrics_out, "decode") if args.metrics_out
              else ""),
    )
    worker.start()

    cfg, params = _make()
    backend = DenseBackend(params, cfg, n_slots=2, max_seq=MAX_SEQ)
    engine = ServingEngine(backend, prefill_chunk=STREAM_CHUNK,
                           prefix_cache=PrefixCache(STREAM_CHUNK))
    ep = Endpoint()
    pw = PrefillWorker(engine, ep, "127.0.0.1", port_q.get(timeout=60))

    # one cold prompt, then two sharing its first 8 tokens (a 2-chunk
    # "system prompt"): the second and third resume from the cache
    rng = np.random.default_rng(7)
    p0 = rng.integers(0, cfg.vocab, STREAM_PROMPT_LEN).astype(np.int32)
    prompts = [
        p0,
        np.concatenate([p0[:8], rng.integers(0, cfg.vocab, 4).astype(np.int32)]),
        p0.copy(),
    ]
    pw.submit(prompts[0], max_new_tokens=args.new_tokens)
    pw.drain()  # cold request fully streamed -> its slot parks as a donor
    for p in prompts[1:]:
        pw.submit(p, max_new_tokens=args.new_tokens)
    pw.drain()
    pw.close()

    results, snap = result_q.get(timeout=180)
    worker.join(timeout=60)

    hits = int(obs.counter("prefix_cache_hits_total").get())
    reused = int(obs.counter("prefix_cache_tokens_reused_total").get())
    computed = int(obs.counter("serving_prefill_tokens_total")
                   .get(kind="computed"))
    chunks = int(obs.counter("kv_stream_chunks_total").get(role="tx"))
    wire = obs.counter("p2p_bytes_total").get(verb="write")
    print(
        f"prefill fleet: {len(prompts)} requests, {hits} prefix-cache "
        f"hit(s), {reused} prompt tokens reused / {computed} computed, "
        f"{chunks} KV slabs ({wire / 1e3:.1f} KB) streamed chunk-wise"
    )
    split = {k: snap.get(k, {}).get("p50") for k in
             ("disagg_queue_ms", "disagg_prefill_ms", "disagg_transfer_ms")}
    print(
        f"decode fleet: adopted {snap.get('adopted', 0)} requests; TTFT "
        f"split p50 queue/prefill/transfer = {split['disagg_queue_ms']}/"
        f"{split['disagg_prefill_ms']}/{split['disagg_transfer_ms']} ms"
    )

    # per-role observability dumps: this (prefill) process writes the
    # paths the CLI asked for; the decode process already wrote its
    # _role_path siblings — together they are the fleet-trace inputs
    written = obs.dump_from_args(
        args, extra_lines=ServingMetrics.prometheus_lines(engine.snapshot()),
        process_name="uccl_tpu.prefill",
    )
    for path in written:
        print(f"wrote {path} (+ decode-role sibling "
              f"{_role_path(path, 'decode')})")
    if pw.clock_rtt_s is not None:
        print(f"clock exchange: offset {pw.clock_offset_s * 1e6:+.1f} us, "
              f"rtt {pw.clock_rtt_s * 1e6:.1f} us (decode vs prefill wall)")

    ok = len(results) == STREAM_REQUESTS and hits >= 1
    for prompt, toks, hit in results:
        want = np.asarray(generate(
            params, jnp.asarray(prompt)[None], cfg,
            max_new_tokens=args.new_tokens, max_seq=MAX_SEQ,
        ))[0].tolist()
        if toks != want:
            print(f"MISMATCH (hit={hit}): got {toks} want {want}")
            ok = False
    print(f"disaggregated tokens match single-worker generation: {ok}")
    return 0 if ok else 1


# -- legacy: one-shot whole-cache handoff ------------------------------------
def decode_worker(port_q, result_q, new_tokens):
    """Decode side: advertises cache buffers, receives them, continues."""
    _pin_cpu()
    import jax.numpy as jnp
    import numpy as np

    from uccl_tpu.models.inference import KVCache, decode_step_elastic
    from uccl_tpu.p2p import Endpoint
    from uccl_tpu.serving.disagg import decode_continue

    compress = os.environ.get("UCCL_TPU_EXAMPLE_COMPRESS", "off")
    elastic = os.environ.get("UCCL_TPU_EXAMPLE_ELASTIC") == "1"
    cfg, params = _make()
    ep = Endpoint()
    port_q.put(ep.port)
    conn = ep.accept(timeout_ms=30000)

    # advertise host buffers shaped like the cache the prefill side will send
    shape = (cfg.n_layers, BATCH, MAX_SEQ, cfg.n_kv_heads, cfg.head_dim)
    if compress != "off":
        # compressed blobs land here (reference: DietGPU KV transfer)
        from uccl_tpu.p2p.compress import compressed_bound, decode_any

        raw_bytes = int(np.prod(shape)) * 4
        bound = (
            compressed_bound(shape, np.float32)
            if compress == "fp8"
            else raw_bytes + (1 << 14)  # lossless: raw + header slack
        )
        k_host = np.zeros(bound, np.uint8)
        v_host = np.zeros(bound, np.uint8)
    else:
        k_host = np.zeros(shape, np.float32)
        v_host = np.zeros(shape, np.float32)
    ep.send(conn, ep.advertise(ep.reg(k_host)))
    ep.send(conn, ep.advertise(ep.reg(v_host)))
    # Data-arrival signal rides the NIXL notify pattern (reference
    # p2p/uccl_engine.h:218-226): the prefill side one-sided-writes the
    # cache, then sends a notif carrying (length, first generated token);
    # the decode side drains non-blocking — free to do other work (e.g.
    # serve other requests) between polls.
    deadline = time.monotonic() + 30.0
    while not (notifs := ep.get_notifs(max_n=1)):
        if time.monotonic() > deadline:
            raise TimeoutError("no KV-arrival notif within 30s")
        time.sleep(0.002)
    meta = np.frombuffer(notifs[0][1], np.int32)
    length, first_tok = int(meta[0]), meta[1 : 1 + BATCH]

    if compress != "off":
        k_arr, v_arr = decode_any(k_host), decode_any(v_host)
    else:
        k_arr, v_arr = k_host, v_host
    cache = KVCache(jnp.asarray(k_arr), jnp.asarray(v_arr), jnp.int32(length))
    if elastic:
        # Re-home the received cache elastically: hot ring of 1 block in
        # device memory, the rest of the prefix offloaded to pinned host
        # memory — the decode worker's context is then bounded by host RAM,
        # not HBM (lite-ep's host-window elasticity, TPU-style).
        from uccl_tpu.ep import ElasticKVCache

        ekv = ElasticKVCache.from_cache(cache, block_tokens=8, hot_blocks=1)
        toks = [first_tok]
        tok = jnp.asarray(first_tok)
        for _ in range(new_tokens - 1):
            logits = decode_step_elastic(params, tok, ekv, cfg)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            toks.append(np.asarray(tok))
        print(
            f"decode: elastic cache held {ekv.cold_blocks} cold blocks in "
            f"host memory, {ekv.device_committed_bytes() / 1e3:.1f} KB "
            f"committed HBM, context {ekv.length}"
        )
        result_q.put(np.stack(toks, axis=1))
    else:
        result_q.put(decode_continue(params, cfg, cache, first_tok,
                                     new_tokens))
    ep.close()


def _legacy_main(args) -> int:
    ctx = mp.get_context("spawn")
    port_q, result_q = ctx.Queue(), ctx.Queue()
    worker = ctx.Process(
        target=decode_worker, args=(port_q, result_q, args.new_tokens)
    )
    worker.start()

    import jax.numpy as jnp
    import numpy as np

    from uccl_tpu.models.inference import generate, prefill
    from uccl_tpu.p2p import Endpoint

    cfg, params = _make()
    prompt = jnp.asarray(_prompt())

    # --- prefill worker ---------------------------------------------------
    last_logits, cache = prefill(params, prompt, cfg, max_seq=MAX_SEQ)
    first_tok = np.asarray(jnp.argmax(last_logits, axis=-1), np.int32)

    ep = Endpoint()
    port = port_q.get(timeout=30)
    conn = ep.connect("127.0.0.1", port)
    fifo_k = ep.recv(conn, timeout_ms=30000)
    fifo_v = ep.recv(conn, timeout_ms=30000)
    k_host = np.ascontiguousarray(np.asarray(cache.k, np.float32))
    v_host = np.ascontiguousarray(np.asarray(cache.v, np.float32))
    if args.compress != "off":
        from uccl_tpu.p2p.compress import encode

        k_blob = encode(k_host, args.compress)
        v_blob = encode(v_host, args.compress)
        ep.write(conn, k_blob, fifo_k)  # one-sided compressed cache push
        ep.write(conn, v_blob, fifo_v)
        wire = k_blob.nbytes + v_blob.nbytes
        raw = k_host.nbytes + v_host.nbytes
        print(
            f"prefill: shipped {args.compress} KV cache {wire / 1e6:.3f} MB "
            f"(raw {raw / 1e6:.3f} MB, ratio {raw / wire:.2f}x)"
        )
    else:
        ep.write(conn, k_host, fifo_k)  # one-sided cache push
        ep.write(conn, v_host, fifo_v)
    meta = np.concatenate([[int(cache.length)], first_tok]).astype(np.int32)
    ep.send_notif(conn, np.ascontiguousarray(meta).tobytes())
    if args.compress == "off":
        print(
            f"prefill: shipped KV cache {k_host.nbytes * 2 / 1e6:.2f} MB "
            f"(stats {ep.stats})"
        )

    disagg = result_q.get(timeout=120)
    worker.join(timeout=60)
    ep.close()

    # --- oracle: single-worker generation --------------------------------
    want = np.asarray(
        generate(params, prompt, cfg, max_new_tokens=args.new_tokens, max_seq=MAX_SEQ)
    )
    if args.compress == "fp8":
        # fp8 KV is lossy; exact token equality is not guaranteed. Require
        # generation to complete and mostly agree with the oracle.
        agree = float(np.mean(disagg == want))
        print(f"disaggregated (fp8 wire) token agreement: {agree:.0%}")
        if disagg.shape != want.shape or agree < 0.5:
            return 1
    else:
        # raw and lossless wires are exact: tokens must match bit-for-bit
        ok = np.array_equal(disagg, want)
        print(f"disaggregated tokens match single-worker generation: {ok}")
        if not ok:
            print("disagg:", disagg)
            print("want:  ", want)
            return 1
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument(
        "--compress", nargs="?", const="fp8", default="off",
        choices=["off", "fp8", "lossless"],
        help="LEGACY one-shot handoff with a compressed wire: fp8 (lossy "
             "~3.8x) or lossless (exact, byte-plane + native rANS)",
    )
    ap.add_argument(
        "--elastic", action="store_true",
        help="LEGACY one-shot handoff decoding over an elastic KV cache "
             "(cold blocks in host memory)",
    )
    ap.add_argument(
        "--one-shot", action="store_true",
        help="run the legacy whole-cache handoff instead of the "
             "chunk-streamed serving pair",
    )
    from uccl_tpu import obs

    obs.add_cli_args(ap)
    args = ap.parse_args()
    if args.compress != "off":
        os.environ["UCCL_TPU_EXAMPLE_COMPRESS"] = args.compress
    if args.elastic:
        os.environ["UCCL_TPU_EXAMPLE_ELASTIC"] = "1"
    _pin_cpu()
    from uccl_tpu.utils.device import describe

    print(f"device: {describe()}", flush=True)
    obs.setup_from_args(args)
    obs.dump_at_exit(args)

    if args.compress != "off" or args.elastic or args.one_shot:
        sys.exit(_legacy_main(args))
    sys.exit(_stream_main(args))


if __name__ == "__main__":
    main()
