"""Wall-clock fleet prefix-cache bench: K prefill workers, one directory.

ISSUE 19 tentpole proof. K worker PROCESSES (real process isolation, not
threads) each run a ServingEngine + PrefixCache + FleetWorker over one
shared p2p store. Worker 0 computes a shared system prefix once; every
other worker's first request finds it in the fleet directory and pulls
the KV rows over the T2 wire path instead of recomputing — the bench
audits that cut with counter deltas, never with prints alone.

Arms (all run the same shared-prefix working set):

* ``no_directory`` — baseline: engines + local prefix caches only. Each
  worker recomputes the shared prefix cold on its first request.
* ``directory``    — FleetWorker attached: worker 0 seeds, workers 1..K
  import the prefix cross-process (``fleet_cache_hits_total``,
  ``p2p_bytes_total{verb="kv_tier"}`` deltas), computing strictly fewer
  prefill tokens and reaching first token sooner.
* ``chaos``        — worker 0 seeds then dies (``os._exit``) with its
  directory entries resident. Survivors dial the corpse (counted
  ``fleet_cache_errors_total{reason="dial"}``), sweep its entries via
  ``invalidate_owner`` (counted invalidations), and finish every
  request cold — conservation and bit-exactness hold.

Every finished request in every arm is replayed against the one-shot
``models.inference.generate`` oracle in the parent — the fleet path is
lossless or the bench exits non-zero.

Per-role observability: ``--metrics-out x.prom`` writes one prom per
worker (``x.<arm>-wN.prom``) plus the federated directory-arm snapshot
at ``x.prom`` via obs/aggregate; ``--trace-out`` writes per-role Chrome
traces merged through scripts/trace_merge.py. ``scripts/check_obs.py
--fleet-cache`` gates the JSON + prom in qa/ci.

Usage::

    python benchmarks/fleet_bench.py --smoke \
        --metrics-out /tmp/fleet.prom --json-out /tmp/fleet.json
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

CHUNK = 8
PREFIX_CHUNKS = 20           # shared system prefix = 160 tokens
SUFFIX_LEN = CHUNK           # per-request tail = 1 more chunk
MAX_SEQ = 192
CFG_KW = dict(vocab=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
              head_dim=8, ffn=64)

_PREFIX_LEN = CHUNK * PREFIX_CHUNKS


def _role_path(path: str, role: str) -> str:
    stem, ext = os.path.splitext(path)
    return f"{stem}.{role}{ext}"


def _make_model():
    import jax

    from uccl_tpu.models import dense

    cfg = dense.DenseConfig(**CFG_KW)
    params = dense.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _shared_prefix(vocab: int) -> np.ndarray:
    rng = np.random.default_rng(1234)
    return rng.integers(0, vocab, _PREFIX_LEN).astype(np.int32)


def _suffix(vocab: int, idx: int, r: int) -> np.ndarray:
    rng = np.random.default_rng(1000 + idx * 100 + r)
    return rng.integers(0, vocab, SUFFIX_LEN).astype(np.int32)


def _seed_prompt(vocab: int) -> np.ndarray:
    rng = np.random.default_rng(999)
    return np.concatenate([_shared_prefix(vocab),
                           rng.integers(0, vocab, SUFFIX_LEN)
                           .astype(np.int32)])


def _counters():
    from uccl_tpu import obs

    return {
        "computed": obs.counter("serving_prefill_tokens_total")
        .get(kind="computed"),
        "skipped": obs.counter("serving_prefill_tokens_total")
        .get(kind="skipped"),
        "hits": obs.counter("fleet_cache_hits_total").get(),
        "stale": obs.counter("fleet_cache_stale_total").get(),
        "imported_tokens": obs.counter("fleet_cache_tokens_imported_total")
        .get(),
        "kv_tier_bytes": obs.counter("p2p_bytes_total").get(verb="kv_tier"),
        "dial_errors": obs.counter("fleet_cache_errors_total")
        .get(reason="dial"),
        "invalidations": obs.counter("fleet_dir_invalidations_total").get(),
    }


def fleet_worker(idx: int, arm: str, n_requests: int,
                 new_tokens: int, store_port: int, result_q,
                 trace_out: str, metrics_out: str,
                 flight_dir: str = "") -> None:
    """One prefill-worker process: engine + cache (+ FleetWorker)."""
    from uccl_tpu import obs
    from uccl_tpu.p2p import Endpoint
    from uccl_tpu.p2p.store import StoreClient
    from uccl_tpu.serving import (
        DenseBackend, PrefixCache, ServingEngine, ServingMetrics,
    )
    from uccl_tpu.serving.fleet import FleetWorker

    if trace_out:
        obs.enable_tracing()
    recorder = None
    if flight_dir:
        # per-process recorder: the chaos arm's survivors must each land
        # EXACTLY ONE peer_dead bundle when they dial the corpse (the
        # dial-failure and fail-latch sites share the key fleet:<owner>,
        # so the recorder dedupes them into one), clean arms none
        from uccl_tpu.obs import flight as flight_mod

        recorder = flight_mod.enable(
            os.path.join(flight_dir, f"{arm}-w{idx}"))

    cfg, params = _make_model()
    eng = ServingEngine(
        DenseBackend(params, cfg, n_slots=3, max_seq=MAX_SEQ),
        prefill_chunk=CHUNK, prefix_cache=PrefixCache(CHUNK),
    )
    sc = StoreClient("127.0.0.1", store_port)

    # compile warmup BEFORE the fleet attaches: the warmup parks stay
    # private local donors, never published directory entries. The second
    # prompt re-uses the first one's prefix at the measured depth so the
    # T0 copy path (and its jit) is hot before any timed request
    warm = np.random.default_rng(42).integers(
        0, cfg.vocab, _PREFIX_LEN + 4).astype(np.int32)
    warm2 = np.concatenate([warm[:_PREFIX_LEN],
                            np.random.default_rng(43).integers(
                                0, cfg.vocab, 4).astype(np.int32)])
    for w in (warm, warm2):
        eng.submit(w, max_new_tokens=new_tokens)
        eng.drain()
    # warm the KV import jit on a free slot at the measured depth — the
    # slot's rows/lens are rewritten by its next admission, so this is
    # invisible to correctness (fleet hits land via the same call)
    rows = eng.backend.export_slot_kv(2, 0, _PREFIX_LEN)
    eng.backend.import_slot_kv(2, rows[0], rows[1], length=_PREFIX_LEN)
    eng.reset_metrics()

    fw = None
    if arm != "no_directory":
        fw = FleetWorker(f"w{idx}", sc, Endpoint(), chunk=CHUNK,
                         capacity_bytes=1 << 22, max_entry_bytes=1 << 22,
                         fail_limit=2, timeout_ms=8000)
        eng.attach_fleet(fw)

    def run_one(prompt):
        req = eng.submit(prompt, max_new_tokens=new_tokens)
        eng.drain()
        return req

    # -- warm phase: worker 0 computes the shared prefix once ---------------
    if idx == 0:
        run_one(_seed_prompt(cfg.vocab))
        sc.set(f"bench/{arm}/warm", b"1")
        if arm == "chaos":
            # die with directory entries resident — no close(), no
            # withdraw: the crash the survivors must absorb
            sc.wait(f"bench/{arm}/die", timeout_s=120)
            os._exit(0)
    else:
        sc.wait(f"bench/{arm}/warm", timeout_s=120)
        if arm == "chaos":
            sc.wait(f"bench/{arm}/dead", timeout_s=120)
        elif fw is not None:
            # steady-state TTFT: peers in a long-lived fleet dial each
            # other once and reuse the channel for every fetch after —
            # establish it here so the measured window times the fetch
            # path, not one TCP/Channel handshake
            fw.client._remote_for("w0")

    # -- measured batch -----------------------------------------------------
    c0 = _counters()
    reqs, invalidated = [], 0
    for r in range(n_requests):
        prompt = np.concatenate([_shared_prefix(cfg.vocab),
                                 _suffix(cfg.vocab, idx, r)])
        req = run_one(prompt)
        reqs.append(req)
        if arm == "chaos" and idx != 0 and r == 0:
            # first request dialed the corpse and fell back cold; now
            # sweep the dead owner's entries like the heartbeat plane
            # declaring it dead (idempotent across survivors)
            invalidated = fw.invalidate_owner("w0")
    c1 = _counters()

    snap = eng.snapshot()
    report = {
        "idx": idx,
        "requests": [
            {"prompt": np.asarray(q.prompt).tolist(),
             "out": [int(t) for t in q.out_tokens],
             "n_generated": int(q.n_generated),
             "ttft_ms": round(float(q.ttft) * 1e3, 3),
             "hit_len": int(q.cache_hit_len)}
            for q in reqs
        ],
        "batch": {k: c1[k] - c0[k] for k in c0},
        "invalidated": invalidated,
        # worker 0's seed request completed before the measured window
        "completed_expected": len(reqs) + (1 if idx == 0 else 0),
        "completed": int(snap["completed"]),
        "leaked": int(eng.pool.leaked()),
        "flight_bundles": (sorted(os.path.basename(p)
                                  for p in recorder.bundles)
                           if recorder is not None else []),
    }
    if metrics_out:
        obs.write_metrics(
            _role_path(metrics_out, f"{arm}-w{idx}"),
            extra_lines=ServingMetrics.prometheus_lines(snap),
        )
    if trace_out:
        obs.write_trace(_role_path(trace_out, f"{arm}-w{idx}"),
                        process_name=f"uccl_tpu.fleet.{arm}.w{idx}")
    result_q.put(report)
    if fw is not None:
        fw.close()
        fw.ep.close()
    sc.close()


def _oracle_check(cfg, params, reports, cache) -> bool:
    import jax.numpy as jnp

    from uccl_tpu.models.inference import generate

    ok = True
    for rep in reports:
        for q in rep["requests"]:
            key = tuple(q["prompt"]) + (len(q["out"]),)
            if key not in cache:
                toks = generate(params, jnp.asarray(
                    np.asarray(q["prompt"], np.int32))[None], cfg,
                    max_new_tokens=len(q["out"]), max_seq=MAX_SEQ)
                cache[key] = np.asarray(toks)[0].tolist()
            if q["out"] != cache[key][: len(q["out"])]:
                print(f"ORACLE MISMATCH w{rep['idx']}: got {q['out']} "
                      f"want {cache[key][: len(q['out'])]}")
                ok = False
    return ok


def run_arm(arm: str, *, n_workers: int, n_requests: int, new_tokens: int,
            trace_out: str, metrics_out: str, oracle_cache,
            flight_dir: str = "") -> dict:
    from uccl_tpu.p2p.store import StoreClient, StoreServer

    cfg, params = _make_model()
    srv = StoreServer()
    ctx = mp.get_context("spawn")
    result_q = ctx.Queue()
    procs = [
        ctx.Process(target=fleet_worker,
                    args=(i, arm, n_requests, new_tokens,
                          srv.port, result_q, trace_out, metrics_out,
                          flight_dir))
        for i in range(n_workers)
    ]
    t0 = time.perf_counter()
    for p in procs:
        p.start()

    coord = StoreClient("127.0.0.1", srv.port)
    if arm == "chaos":
        coord.wait(f"bench/{arm}/warm", timeout_s=120)
        coord.set(f"bench/{arm}/die", b"1")
        procs[0].join(timeout=60)
        # only after the corpse is truly gone may survivors dial it
        coord.set(f"bench/{arm}/dead", b"w0")

    expect = n_workers - 1 if arm == "chaos" else n_workers
    reports = [result_q.get(timeout=300) for _ in range(expect)]
    for p in procs:
        p.join(timeout=60)
    alive = [p for p in procs if p.is_alive()]
    for p in alive:
        p.terminate()
    coord.close()
    srv.close()
    wall_s = time.perf_counter() - t0

    batch = {k: sum(r["batch"][k] for r in reports)
             for k in reports[0]["batch"]}
    ttfts = [q["ttft_ms"] for r in reports for q in r["requests"]]
    non_owner = [r for r in reports if r["idx"] != 0]
    cross_reqs = sum(len(r["requests"]) for r in non_owner)
    oracle_exact = _oracle_check(cfg, params, reports, oracle_cache)
    conserved = (not alive and all(r["leaked"] == 0 for r in reports)
                 and all(r["completed"] == r["completed_expected"]
                         for r in reports))
    flight_ok = True
    flight_bundles = {f"w{r['idx']}": r.get("flight_bundles", [])
                      for r in reports}
    if flight_dir:
        # exactly one attributable dump per injected fault, zero on
        # clean arms: each chaos survivor dials the corpse once and must
        # land a single peer_dead bundle; no fault -> no bundle
        for r in reports:
            names = r.get("flight_bundles", [])
            if arm == "chaos" and r["idx"] != 0:
                want = (len(names) == 1
                        and names[0].endswith("_peer_dead.json"))
            else:
                want = not names
            if not want:
                print(f"FLIGHT MISMATCH arm={arm} w{r['idx']}: "
                      f"bundles={names}")
                flight_ok = False
    summary = {
        "arm": arm,
        "workers": n_workers,
        "requests": sum(len(r["requests"]) for r in reports),
        "computed_prefill_tokens": int(batch["computed"]),
        "skipped_prefill_tokens": int(batch["skipped"]),
        "fleet_hits": int(batch["hits"]),
        "fleet_stale": int(batch["stale"]),
        "fleet_tokens_imported": int(batch["imported_tokens"]),
        "kv_tier_bytes": int(batch["kv_tier_bytes"]),
        "dial_errors": int(batch["dial_errors"]),
        "invalidations": int(sum(r["invalidated"] for r in reports)),
        "cross_hit_rate": (round(batch["hits"] / cross_reqs, 4)
                           if cross_reqs else 0.0),
        "ttft_ms_mean": round(float(np.mean(ttfts)), 3),
        "ttft_ms_by_worker": {
            f"w{r['idx']}": [q["ttft_ms"] for q in r["requests"]]
            for r in reports},
        "oracle_exact": bool(oracle_exact),
        "conserved": bool(conserved),
        "flight_ok": bool(flight_ok),
        "flight_bundles": flight_bundles,
        "wall_s": round(wall_s, 2),
    }
    print("bench=serving_fleet " + " ".join(
        f"{k}={v}" for k, v in summary.items()))
    return summary


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=2,
                    help="prefill worker processes sharing one directory")
    ap.add_argument("--requests", type=int, default=2,
                    help="measured requests per worker (after the seed)")
    ap.add_argument("--new-tokens", type=int, default=4)
    ap.add_argument("--arms", default="no_directory,directory,chaos")
    ap.add_argument("--smoke", action="store_true",
                    help="CI shape: 2 workers x 2 requests, all arms")
    ap.add_argument("--metrics-out", default="")
    ap.add_argument("--json-out", default="")
    ap.add_argument("--trace-out", default="")
    ap.add_argument("--flight-dir", default="",
                    help="arm a per-worker flight recorder (bundles in "
                         "<dir>/<arm>-wN/); the chaos arm must dump "
                         "exactly one peer_dead per survivor, clean "
                         "arms none")
    args = ap.parse_args()
    if args.smoke:
        args.workers, args.requests = 2, 2

    if args.workers < 2:
        print("need --workers >= 2 (cross-worker reuse is the point)")
        return 2

    # N worker processes plus this parent all run JAX, and what is measured
    # is the host p2p wire between them: every one is pinned to the CPU
    # (the spawned workers inherit the pin) so none holds a chip
    from uccl_tpu.utils.device import describe, pin_cpu

    pin_cpu()
    print(f"device: {describe()}", flush=True)

    oracle_cache: dict = {}
    arms = {}
    for arm in [a.strip() for a in args.arms.split(",") if a.strip()]:
        arms[arm] = run_arm(
            arm, n_workers=args.workers, n_requests=args.requests,
            new_tokens=args.new_tokens, trace_out=args.trace_out,
            metrics_out=args.metrics_out, oracle_cache=oracle_cache,
            flight_dir=args.flight_dir)

    ok = all(a["oracle_exact"] and a["conserved"] and a["flight_ok"]
             for a in arms.values())
    if "directory" in arms and "no_directory" in arms:
        d, b = arms["directory"], arms["no_directory"]
        saved = b["computed_prefill_tokens"] - d["computed_prefill_tokens"]
        print(f"fleet directory: {d['fleet_hits']} cross-worker hit(s), "
              f"{saved} prefill tokens saved, TTFT "
              f"{b['ttft_ms_mean']} -> {d['ttft_ms_mean']} ms")
        ok = ok and d["fleet_hits"] >= 1 and saved > 0

    if args.metrics_out and "directory" in arms:
        # federate the directory-arm worker proms the way a Prometheus
        # scrape would (counters sum, gauges stay per-replica)
        from uccl_tpu.obs.aggregate import aggregate, fleet_text

        scrapes = []
        for i in range(args.workers):
            path = _role_path(args.metrics_out, f"directory-w{i}")
            with open(path) as f:
                scrapes.append((f"w{i}", f.read()))
        with open(args.metrics_out, "w") as f:
            f.write(fleet_text(aggregate(scrapes)))
        print(f"wrote {args.metrics_out} (+ per-worker role siblings)")

    if args.trace_out and "directory" in arms:
        inputs = [_role_path(args.trace_out, f"directory-w{i}")
                  for i in range(args.workers)]
        merge = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__))), "scripts", "trace_merge.py"),
             "--out", args.trace_out] + inputs)
        ok = ok and merge.returncode == 0

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"bench": "serving_fleet", "workers": args.workers,
                       "requests_per_worker": args.requests,
                       "new_tokens": args.new_tokens,
                       "arms": arms}, f, indent=1)
        print(f"wrote {args.json_out}")

    print(f"fleet bench {'OK' if ok else 'FAILED'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
