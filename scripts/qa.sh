#!/bin/bash
# Full CPU-runnable acceptance ladder in one command — everything the repo
# can prove without a chip. Mirrors CI plus the example workloads the
# driver/judge spot-check. What needs the chip (chip_smoke.py, bench.py) is
# not here: those refuse to run on the CPU.
#
# Usage: scripts/qa.sh [quick]   (quick = suite + native tests only)
set -u
cd "$(dirname "$0")/.."
fail=0
note() { echo; echo "=== $* ==="; }
check() { if [ "$1" -ne 0 ]; then echo "^^^ FAILED"; fail=1; fi; }

note "pallas kernel smoke tier (interpret-mode, fail-fast: a2a proof --chunks 2 + oracle tests)"
timeout 300 python scripts/pallas_a2a_proof.py --chunks 2; check $?
timeout 900 python -m pytest tests/test_pallas_a2a.py tests/test_pallas_ccl.py -q; check $?

note "quantized-wire smoke tier (interpret-mode fp8 arms: ring allreduce + EP roundtrip error-bounded, pallas == lax bit-identity, wire_dtype-labeled byte series exported)"
timeout 300 python scripts/pallas_a2a_proof.py --wire-dtype fp8 \
  --metrics-out /tmp/qa_quant_metrics.prom; check $?
python scripts/check_obs.py --quant /tmp/qa_quant_metrics.prom fp8; check $?

note "planner smoke tier (interpret-mode bidir allreduce: decision on collective_plan_total, bench arm labeled off the counter, oracle-exact vs the numpy sum oracle)"
timeout 300 python benchmarks/all_reduce_perf.py --devices 4 --algo bidir \
  --json --check --min-bytes 4096 --max-bytes 4096 --iters 2 \
  --metrics-out /tmp/qa_plan_metrics.prom > /tmp/qa_plan_bench.json; check $?
python scripts/check_obs.py --plan /tmp/qa_plan_metrics.prom /tmp/qa_plan_bench.json; check $?

note "scheduled a2a smoke tier (interpret-mode Zipf-skewed routing at world 4: Birkhoff rounds pinned on, recv bit-identical to the fixed-stream anchor, plan/rounds/skew series counter-audited)"
timeout 300 python benchmarks/ep_bench.py --devices 4 --tokens 16 --hidden 64 \
  --experts 8 --topk 2 --iters 1 --skew 1.2 --a2a-sched on \
  --metrics-out /tmp/qa_sched_metrics.prom > /tmp/qa_sched_bench.json; check $?
python scripts/check_obs.py --a2a-sched /tmp/qa_sched_metrics.prom /tmp/qa_sched_bench.json; check $?

note "bcast/allgather + fleet weight-push smoke tier (planned verbs oracle-exact + labeled off the verb-labeled plan counter; relay push: every peer bit-exact, root egress = one snapshot)"
timeout 300 python benchmarks/all_reduce_perf.py --devices 4 --bench bcast,ag \
  --json --check --min-bytes 16384 --max-bytes 16384 --iters 2 \
  --metrics-out /tmp/qa_bcastag_metrics.prom > /tmp/qa_bcastag_bench.json; check $?
timeout 300 python benchmarks/weight_push_bench.py --smoke \
  --metrics-out /tmp/qa_push_metrics.prom --json-out /tmp/qa_push_bench.json; check $?
python scripts/check_obs.py --weights /tmp/qa_push_metrics.prom /tmp/qa_bcastag_metrics.prom; check $?

note "serving engine smoke tier (fail-fast: 2 slots, 6 mixed-length requests, oracle match + no leaked slots)"
JAX_PLATFORMS=cpu timeout 600 python -m uccl_tpu.serve --server --devices 2 --slots 2 \
  --requests 6 --prompt-len 8 --new-tokens 4 --arrival-rate 50 --check-oracle; check $?
note "serving engine smoke tier, chunked prefill (8-token chunks over 12-token prompts: multi-chunk resume + oracle match)"
JAX_PLATFORMS=cpu timeout 600 python -m uccl_tpu.serve --server --devices 2 --slots 2 \
  --requests 6 --prompt-len 12 --new-tokens 4 --arrival-rate 50 \
  --prefill-chunk 8 --check-oracle; check $?

note "speculative decoding smoke tier (4 slots, spec_k=2, NGram drafter: oracle-exact + >=1 accepted speculation counted)"
JAX_PLATFORMS=cpu timeout 600 python -m uccl_tpu.serve --server --devices 2 --slots 4 \
  --requests 8 --prompt-len 8 --new-tokens 16 --arrival-rate 50 --spec-k 2 \
  --check-oracle --metrics-out /tmp/qa_spec_metrics.prom; check $?
python scripts/check_obs.py --spec /tmp/qa_spec_metrics.prom; check $?

note "replica router + preemption smoke tier (2 replicas, 2 SLO classes, batch-first overload: oracle-exact, >=1 preemption counted, routing + per-class series validated)"
JAX_PLATFORMS=cpu timeout 600 python -m uccl_tpu.serve --server --devices 2 --stack dense --slots 2 \
  --replicas 2 --priority-classes --class-pattern batch-first --prefill-chunk 4 \
  --requests 12 --prompt-len 12 --new-tokens 24 --arrival-rate 100 --check-oracle \
  --metrics-out /tmp/qa_router_metrics.prom; check $?
python scripts/check_obs.py --router /tmp/qa_router_metrics.prom; check $?

note "tiered KV cache smoke tier (2 device slots vs 6-prefix working set, t0/t1/t1-fp8/t1-t2 arms over a 4-entry host pool: demote->promote cycles per tier counter-audited, lossless arms oracle-exact, resident-bytes gauges live)"
JAX_PLATFORMS=cpu timeout 600 python benchmarks/serving_bench.py --rates 50 --slots 2 \
  --prefill-chunks 4 --kv-tiers t0,t1,t1-fp8,t1-t2 --working-sets 3 \
  --host-tier-entries 4 --requests 24 --prompt-len 12 --shared-prefix-len 8 \
  --new-tokens 4 --check-oracle \
  --metrics-out /tmp/qa_kvtiers_metrics.prom > /tmp/qa_kvtiers_bench.json; check $?
python scripts/check_obs.py --kv-tiers /tmp/qa_kvtiers_metrics.prom /tmp/qa_kvtiers_bench.json; check $?

note "multi-tenant isolation smoke tier (8 tenants + t0 burst-flooding, per-tenant LoRA via a 4-row adapter store: fair-on victim SLO >= 0.9x baseline, fair-off visibly collapsed, tenant/adapter series counter-audited)"
JAX_PLATFORMS=cpu timeout 600 python benchmarks/serving_bench.py --rates 40 --slots 2 \
  --prefill-chunks off --tenants 8 --overload-tenant --adapter-rank 2 \
  --requests 48 --prompt-len 8 --new-tokens 32 --slo-ttft-ms 250 --slo-tpot-ms 100 \
  --metrics-out /tmp/qa_tenants_metrics.prom > /tmp/qa_tenants_bench.json; check $?
python scripts/check_obs.py --tenants /tmp/qa_tenants_metrics.prom /tmp/qa_tenants_bench.json; check $?

note "sampled serving smoke tier (temperature/top-p/top-k + per-request seeds across 3 tenants with rank-2 adapters: every request bit-exact vs the sampled W+BA oracle)"
JAX_PLATFORMS=cpu timeout 600 python -m uccl_tpu.serve --server --devices 2 --slots 2 \
  --requests 8 --prompt-len 8 --new-tokens 8 --arrival-rate 50 \
  --temperature 0.8 --top-p 0.9 --top-k 20 --tenants 3 --adapter-rank 2 \
  --check-oracle; check $?

note "windowed transport smoke tier (lossy+reordering loopback incast: 4->1 channel fan-in at 2% drop / 20% reorder, swift + eqds-credit arms, payload bit-exact, SACK retx split + credit series validated)"
timeout 600 python benchmarks/incast_bench.py --smoke \
  --metrics-out /tmp/qa_transport_metrics.prom \
  --json-out /tmp/qa_transport_bench.json; check $?
python scripts/check_obs.py --transport /tmp/qa_transport_metrics.prom /tmp/qa_transport_bench.json; check $?

note "chaos smoke tier (1 of 2 replicas killed mid-run + 5% control-notif drop + 5% data drop + post-GRANT kill: recovered outputs oracle-exact, extended conservation incl. lost, >=1 reclaimed lease, zero leaked slots — all counter-audited; flight recorder armed: one attributable post-mortem bundle per injected fault class, doctor root causes match, clean phase dumps nothing)"
rm -rf /tmp/qa_flight && mkdir -p /tmp/qa_flight
JAX_PLATFORMS=cpu timeout 600 python benchmarks/chaos_bench.py --smoke \
  --flight-dir /tmp/qa_flight \
  --metrics-out /tmp/qa_chaos_metrics.prom --json-out /tmp/qa_chaos_bench.json; check $?
python scripts/check_obs.py --chaos /tmp/qa_chaos_metrics.prom /tmp/qa_chaos_bench.json; check $?
python scripts/check_obs.py --flight /tmp/qa_chaos_metrics.prom /tmp/qa_chaos_bench.json; check $?

note "disagg serving smoke tier (prefill+decode worker pair over p2p: chunk-streamed KV, >=1 prefix-cache hit, oracle-exact, telemetry validated; per-role trace/metrics dumps feed the fleet tier below)"
timeout 600 python examples/disagg_kv.py \
  --trace-out /tmp/qa_fleet_trace.json --metrics-out /tmp/qa_disagg_metrics.prom; check $?
python scripts/check_obs.py --disagg /tmp/qa_disagg_metrics.prom; check $?

note "fleet tracing smoke tier (merge the 2 processes' traces clock-aligned, federate their metrics: >=1 flow-linked cross-process request timeline, BEGIN<=GRANT<=FINAL after alignment, fleet histogram p50/p95 within one bucket of the per-replica sample percentiles)"
python scripts/trace_merge.py --out /tmp/qa_fleet_merged.json \
  /tmp/qa_fleet_trace.json /tmp/qa_fleet_trace.decode.json; check $?
python -m uccl_tpu.obs.aggregate --out /tmp/qa_fleet.prom \
  prefill=/tmp/qa_disagg_metrics.prom decode=/tmp/qa_disagg_metrics.decode.prom; check $?
python scripts/check_obs.py --fleet /tmp/qa_fleet_merged.json /tmp/qa_fleet.prom; check $?

note "fleet prefix-cache smoke tier (2 prefill-worker processes over one directory: a prefix computed on worker 0 lands as a counter-audited cross-worker hit on worker 1 with fewer computed prefill tokens + lower TTFT than the no-directory arm, chaos arm kills the owner mid-stream with its entries invalidated + exactly one peer_dead flight bundle per survivor, every arm oracle-exact)"
rm -rf /tmp/qa_fleet_flight && mkdir -p /tmp/qa_fleet_flight
JAX_PLATFORMS=cpu timeout 600 python benchmarks/fleet_bench.py --smoke \
  --flight-dir /tmp/qa_fleet_flight \
  --metrics-out /tmp/qa_fleetcache_metrics.prom \
  --json-out /tmp/qa_fleetcache_bench.json; check $?
python scripts/check_obs.py --fleet-cache /tmp/qa_fleetcache_metrics.prom /tmp/qa_fleetcache_bench.json; check $?

note "observability smoke tier (2-slot serving run traced end to end: Chrome-trace lifecycle timelines + Prometheus metrics validate)"
JAX_PLATFORMS=cpu timeout 600 python -m uccl_tpu.serve --server --devices 2 --slots 2 \
  --requests 6 --prompt-len 8 --new-tokens 4 --arrival-rate 50 --check-oracle \
  --trace-out /tmp/qa_obs_trace.json --metrics-out /tmp/qa_obs_metrics.prom; check $?
python scripts/check_obs.py /tmp/qa_obs_trace.json /tmp/qa_obs_metrics.prom; check $?

note "pytest (full suite, virtual 8-device mesh; pallas kernel files ran in the smoke tier)"
timeout 2700 python -m pytest tests/ -q \
  --ignore=tests/test_pallas_a2a.py --ignore=tests/test_pallas_ccl.py; check $?

note "native substrate + engine tests"
timeout 900 make -C native test; check $?
note "native tests under ThreadSanitizer"
timeout 900 make -C native tsan; check $?
note "native tests under ASan+UBSan"
timeout 900 make -C native asan; check $?
note "net-plugin allreduce acceptance (dlopen vtable, 4 ranks)"
timeout 900 make -C native perf; check $?

if [ "${1:-}" != "quick" ]; then
  note "examples: disagg KV (legacy one-shot handoff: exact + lossless wires; the streaming pair ran in the smoke tier)"
  timeout 900 python examples/disagg_kv.py --one-shot; check $?
  timeout 900 python examples/disagg_kv.py --compress lossless; check $?
  note "examples: 2-pod hierarchical allreduce"
  timeout 900 python examples/multipod_allreduce.py; check $?
  note "examples: DDP (mesh + process ranks)"
  timeout 900 python examples/ddp_train.py --devices 2 --steps 4 --batch 8; check $?
  timeout 900 python examples/ddp_train.py --processes 2 --steps 4 --batch 8; check $?
  note "examples: RL weight sync"
  timeout 900 python examples/rl_weight_sync.py; check $?
  note "examples: Ray-style actor weight transfer (XferEndpoint)"
  timeout 900 python examples/ray_weight_transfer.py; check $?
  note "examples: vLLM-style disagg proxy (HTTP routing + READ-pull KV)"
  timeout 900 python examples/disagg_proxy.py; check $?
  note "UDP-wire loss study (fig E: engine SACK recovery under packet loss)"
  timeout 1200 python benchmarks/artifact_sweep.py --figs E --iters 2; check $?
  note "trainer + serve handoff"
  rm -rf /tmp/qa_ck
  timeout 900 python -m uccl_tpu.train --devices 8 --mesh dp=2,cp=2,tp=2 \
    --batch 4 --seq 32 --steps 2 --log-every 0 \
    --ckpt-dir /tmp/qa_ck --ckpt-every 2; check $?
  timeout 900 python -m uccl_tpu.serve --devices 8 --ckpt-dir /tmp/qa_ck \
    --batch 8 --prompt-len 6 --new-tokens 8; check $?
fi

echo
if [ "$fail" -eq 0 ]; then echo "QA LADDER: ALL GREEN"; else echo "QA LADDER: FAILURES ABOVE"; fi
exit $fail
